"""Every Pallas kernel family must compile under Mosaic for the v5e — checked
WITHOUT a chip: libtpu can compile for a TPU topology description on a CPU
host (tests/aot_tpu_compile.py).  This is a compile check only (lowering,
Mosaic passes, VMEM fit at GPT-2-small geometry); that the compiled kernels
compute the right numbers is `chip_smoke.py`'s job on the chip."""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def test_kernel_families_compile_for_v5e_topology():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "aot_tpu_compile.py")],
        capture_output=True, text=True, timeout=600, env=env)
    if proc.returncode == 77:
        pytest.skip(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-1500:]
    ok = {line.split()[1] for line in proc.stdout.splitlines()
          if line.startswith("OK ")}
    assert ok == {"flash_fwd", "flash_bwd", "paged_decode",
                  "paged_window_verify", "paged_window_prefill",
                  "paged_tree"}, proc.stdout
