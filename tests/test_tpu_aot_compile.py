"""Every Pallas kernel family must compile under Mosaic for the v5e — checked
WITHOUT a chip: libtpu can compile for a TPU topology description on a CPU
host (tests/aot_tpu_compile.py).  This is a compile check only (lowering,
Mosaic passes, VMEM fit at GPT-2-small geometry and, for the grouped
matmuls and the whole expert layer with its row kernels, at the LFM2
layer's real shapes; one whole LFM2-MoE train step at small widths; the
serve engine's decode and prefill programs at three benchmark cells' real
sizes, held to one layout of the page pool and the chip's memory); that
the compiled kernels
compute the right numbers is `chip_smoke.py`'s job on the chip."""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


# every Mosaic call of the expert layer, forward and backward
MOE_LAYER = {"moe_gmm", "moe_tgmm", "moe_swiglu", "moe_swiglu_bwd",
             "moe_combine", "moe_unwritten"}
MOE_FORWARD = {"moe_gmm", "moe_swiglu", "moe_combine", "moe_unwritten"}
# the engine's paged decode and prefill programs for GPT-2-medium at the
# serving cell's sizes, over 288 pages (the cell's) and 512
GPT2M_STEPS = {f"gpt2m_{step}_{pages}": kernel
               for pages in (288, 512)
               for step, kernel in (("decode", "paged_decode"),
                                    ("prefill", "paged_prefill"))}


@pytest.fixture(scope="module")
def compiled():
    """One child process compiles every family (the topology is described
    there, never while this file is imported); its report, by line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "aot_tpu_compile.py")],
        capture_output=True, text=True, timeout=600, env=env)
    if proc.returncode == 77:
        pytest.skip(proc.stdout.strip().splitlines()[-1])
    return proc


def _kernel_calls(proc) -> dict:
    """``{family: {kernel name: Mosaic calls in the compiled program}}``
    from the child's ``KERNELS <family> <name>=<n>,...`` lines."""
    return {line.split()[1]: {k: int(n) for k, n in (
        kv.split("=") for kv in line.split()[2].split(","))}
        for line in proc.stdout.splitlines()
        if line.startswith("KERNELS ") and len(line.split()) == 3}


def _pool_line(proc, program: str) -> dict:
    """The child's ``POOL <program> key=value ...`` line, as a dict."""
    lines = [ln.split() for ln in proc.stdout.splitlines()
             if ln.startswith(f"POOL {program} ")]
    assert len(lines) == 1, proc.stdout[-3000:]
    return dict(kv.split("=") for kv in lines[0][2:])


def test_kernel_families_compile_for_v5e_topology(compiled):
    proc = compiled
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-1500:]
    ok = {line.split()[1] for line in proc.stdout.splitlines()
          if line.startswith("OK ")}
    assert ok == {"flash_fwd", "flash_bwd", "flash_bwd_8k", "paged_decode",
                  "paged_window_verify", "paged_window_prefill",
                  "paged_tree", "moe_gmm_up", "moe_gmm_down", "moe_layer",
                  "lfm2_train_step", "latent_decode", "latent_prefill",
                  "laguna_decode", "laguna_prefill",
                  *GPT2M_STEPS}, proc.stdout


@pytest.mark.parametrize("family, kernels", [
    ("flash_fwd", {"flash_fwd"}),
    ("flash_bwd", {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    ("flash_bwd_8k", {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    ("paged_decode", {"paged_decode"}),
    ("paged_window_verify", {"paged_prefill"}),
    ("paged_window_prefill", {"paged_prefill"}),
    ("paged_tree", {"paged_tree"}),
    ("moe_gmm_up", {"moe_gmm", "moe_tgmm"}),
    ("moe_gmm_down", {"moe_gmm", "moe_tgmm"}),
    ("moe_layer", MOE_LAYER),
    ("lfm2_train_step", {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
     | MOE_LAYER),
    ("latent_decode", MOE_FORWARD | {"latent_attn"}),
    ("latent_prefill", MOE_FORWARD | {"latent_attn"}),
    ("laguna_decode", MOE_FORWARD | {"paged_decode", "paged_decode_window"}),
    ("laguna_prefill", MOE_FORWARD | {"paged_prefill",
                                      "paged_prefill_window"}),
    *((program, {kernel}) for program, kernel in GPT2M_STEPS.items())])
def test_each_mosaic_call_carries_its_kernels_name(compiled, family, kernels):
    """The stable names the device trace is read by (PR 26): the compiled
    program's Mosaic custom calls have their ``pallas_call``'s ``name=``
    in the op_name, and a family carries no other family's kernel."""
    assert set(_kernel_calls(compiled).get(family, ())) == kernels, \
        compiled.stdout[-3000:]


def test_the_lfm2_step_runs_flash_forward_once(compiled):
    """Each block of the step is under remat, and the model's policy
    (models/lfm2.py: REMAT_POLICY) keeps flash's output and row
    statistics: the backward pass reads them, nothing it recomputes does,
    and the program libtpu compiles for the v5e holds ONE forward flash
    call for its one attention layer beside one of each backward kernel
    (everything-recomputed holds two)."""
    step = _kernel_calls(compiled).get("lfm2_train_step", {})
    assert [step.get(k) for k in ("flash_fwd", "flash_bwd_dq",
                                  "flash_bwd_dkv")] == [1, 1, 1], \
        compiled.stdout[-3000:]


@pytest.mark.parametrize("program", ["latent_decode", "latent_prefill"])
def test_a_latent_step_program_fits_and_keeps_the_pool_in_place(compiled,
                                                                program):
    """The serve engine's programs for the latent-attention expert family
    at the benchmark cell's sizes (`pangu_moe.serve_closed_2k`: 9.84 GB of
    weights, 1,024 pages of 512 tokens, the kernel backend): within the
    v5e's 15.75 GB, every operation on the page pool in its one row-major
    layout (a second layout means XLA copies the whole 3.4 GB pool, twice
    a program), and absorbed attention as one `latent_attn` Mosaic call a
    layer: no loop over page tiles that carries the running maximum, the
    denominator and the float32 accumulator `f32[.., 128], f32[.., 128],
    f32[.., 128, 512]` through HBM is left (PERF.md section 6, PR 37: the
    134 MB tiles of that loop were 49% of the cell's device step)."""
    fields = _pool_line(compiled, program)
    assert fields["layouts"] == "3,2,1,0", fields
    assert int(fields["copies"]) == 0, fields
    assert int(fields["attn_loops"]) == 0, fields
    assert 13.0e9 < int(fields["bytes"]) < 15.75e9, fields
    assert _kernel_calls(compiled)[program]["latent_attn"] == 5


@pytest.mark.parametrize("program, kernels", [
    ("laguna_decode", {"paged_decode": 2, "paged_decode_window": 3}),
    ("laguna_prefill", {"paged_prefill": 2, "paged_prefill_window": 3})])
def test_a_windowed_step_program_fits_and_keeps_both_pools_in_place(
        compiled, program, kernels):
    """The serve engine's programs for the window-and-full-attention
    expert family at the benchmark cell's sizes (`laguna.serve_mixed_8k`:
    7.74 GB of weights, 1,024 global pages and 128 window pages of 512
    tokens x 1,024 values, the kernel backend): the `(512, 1,024)` page
    block fits the kernels' VMEM (four times `gpt2m.serve_closed`'s),
    the program fits the v5e's 15.75 GB, both pools keep their one
    row-major layout with no `copy` of either's shape and under 1 GB of
    temporaries, and the two layer kinds' attention calls carry their own
    names (two full layers, three sliding ones) beside the expert layers'
    twelve grouped products."""
    fields = _pool_line(compiled, program)
    assert fields["layouts"] == "3,2,1,0", fields
    assert int(fields["copies"]) == 0, fields
    assert int(fields["temp"]) < 1e9, fields
    pools = 2 * (2 * 1025 + 3 * 129) * 512 * 1024 * 2  # K and V, bf16
    weights = 2 * 3869857792
    assert pools + weights < int(fields["bytes"]) < pools + weights + 1e9 \
        < 15.75e9, fields
    calls = _kernel_calls(compiled)[program]
    assert {k: calls[k] for k in kernels} == kernels, calls
    assert calls["moe_gmm"] == 12


@pytest.mark.parametrize("program", list(GPT2M_STEPS))
def test_a_gpt2m_step_program_reads_the_pool_as_it_is_stored(compiled,
                                                             program):
    """`gpt2m.serve_closed`'s two programs (64 slots, 128-token pages, the
    kernel backend): the pool is stored as the paged kernels read it, a
    token row of 16 heads x 64 a line (`generate.KVPages`), so every
    pool-shaped value has ONE layout, no `copy` has the pool's shape (the
    parent had four a program, 8.4 ms each on the chip: PERF.md section 6,
    PR 35), the temporaries stay under 1 GB where the parent's were 10.9
    GB, each layer runs its one Mosaic call, and 512 pages fit the chip
    (they did not compile before)."""
    fields = _pool_line(compiled, program)
    assert fields["layouts"] == "3,2,1,0", fields
    assert int(fields["copies"]) == 0, fields
    assert int(fields["temp"]) < 1e9, fields
    pages = int(program.rsplit("_", 1)[1])
    pool = 2 * 24 * (pages + 1) * 128 * 1024 * 2  # K and V, bf16
    assert pool + 0.7e9 < int(fields["bytes"]) < pool + 1.0e9 < 15.75e9, \
        fields
    assert _kernel_calls(compiled)[program] == {GPT2M_STEPS[program]: 24}
