"""Example-script E2E harness (reference: tools/notebook/tester/
NotebookTestSuite.py discovers + executes every sample notebook; here the
samples are plain scripts under examples/, executed on the CPU test mesh)."""

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(glob.glob(os.path.join(REPO, "examples", "*.py")))


def test_examples_exist():
    assert len(EXAMPLES) >= 5


@pytest.mark.extended
@pytest.mark.parametrize("path", EXAMPLES,
                         ids=[os.path.basename(p) for p in EXAMPLES])
def test_example_runs(path):
    if os.path.basename(path) == "spark_submit_101.py":
        # the Spark-hosted example needs pyspark (optional integration);
        # tests/test_spark_adapter.py::test_spark_submit_e2e runs it under
        # spark-submit wherever pyspark exists
        pytest.importorskip("pyspark")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"),
               PYTHONPATH=REPO)
    code = (f"exec(compile(open({path!r}).read(), {path!r}, 'exec'), "
            f"{{'__file__': {path!r}, '__name__': '__main__'}})")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=420, cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "OK" in r.stdout
