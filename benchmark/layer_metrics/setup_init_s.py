"""Seconds of set-up inside the program before its first step: the duration
of the run's `fit/init` span (from the entry of `fitStream`'s core to where
its step loop begins: `module.init`, the optimizer, `_place_params`,
resume). Programs compiled inside it are counted in `setup_compile_s` too."""

from benchmark import span_reduce


def read(trace, counters, cell):
    return span_reduce.init_seconds(span_reduce.ring())
