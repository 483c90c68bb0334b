"""Go inference API (reference paddle/fluid/inference/goapi): runs the
real `go test` end-to-end when a Go toolchain exists; otherwise verifies
the wrapper's surface parity statically (this image ships no Go — the
underlying C ABI is exercised by test_inference_capi.py regardless)."""
import os
import re
import shutil
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOAPI = os.path.join(REPO, "goapi")


def test_go_wrapper_covers_c_abi_surface():
    """Every PD_* function the C header exports must be referenced by
    the Go wrapper (no silently-unwrapped ABI)."""
    header = open(os.path.join(REPO, "csrc", "pd_inference_c.h")).read()
    exported = set(re.findall(r"\b(PD_\w+)\s*\(", header))
    go_src = "".join(
        open(os.path.join(GOAPI, f)).read()
        for f in os.listdir(GOAPI) if f.endswith(".go"))
    wrapped = set(re.findall(r"C\.(PD_\w+)\(", go_src))
    missing = exported - wrapped
    assert not missing, f"C ABI functions unwrapped in goapi: {missing}"


@pytest.mark.skipif(shutil.which("go") is None,
                    reason="no Go toolchain in this image")
def test_go_end_to_end():
    subprocess.run(["make", "-C", os.path.join(REPO, "csrc"),
                    "inference"], check=True, capture_output=True)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    r = subprocess.run(["go", "test", "-v", "./..."], cwd=GOAPI, env=env,
                       capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, (r.stdout, r.stderr)
