"""Seconds JAX spent compiling or loading programs from its cache before the
window opened (backend-compile and cache-retrieval durations, summed)."""


def read(trace, counters, cell):
    return counters.get("setup_compile_s")
