"""chip_smoke.py, rehearsed where there is no chip.

The script itself only has something to say on a TPU (the chip tool runs it
there); what tier-1 can hold is that (a) its phase functions — the same ones
the chip runs — execute end to end on the CPU at tiny sizes, with the Pallas
kernels in interpret mode because the rehearsal asks for it explicitly, and
(b) the script refuses to report success anywhere JAX finds no TPU.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_phases_rehearse_on_cpu(tmp_path, monkeypatch, capsys):
    import chip_smoke

    # the VGG phase pins the no-download switch for the process; let
    # monkeypatch put the environment back afterwards
    monkeypatch.setenv("TPUDP_NO_DOWNLOAD", "1")
    tiny = chip_smoke.Sizes(
        vgg_batch=8, vgg_windows=2, vgg_test=16,
        lm=dict(vocab_size=64, max_seq_len=128, num_layers=1, num_heads=2,
                d_model=32),
        lm_seq=128,  # a multiple of 128: the flash kernel really runs
        lfm2=dict(vocab_size=64, hidden_size=128, intermediate_size=256,
                  moe_intermediate_size=128, num_hidden_layers=2,
                  num_dense_layers=1,
                  layer_types=("conv", "full_attention"),
                  num_attention_heads=2, num_key_value_heads=1,
                  num_experts=2, num_experts_routed=8,
                  num_experts_per_tok=4),
        lm_batch_per_device=1, serve_slots=2, serve_max_len=64,
        serve_chunk=8, prompt_lens=(5, 8, 19), max_new=6)
    results = chip_smoke.run_phases(tiny, str(tmp_path), rehearse=True)
    assert [r["phase"] for r in results] == [n for n, _ in chip_smoke.PHASES]
    failed = {r["phase"]: r["detail"] for r in results if not r["ok"]}
    assert not failed, failed
    out = capsys.readouterr().out
    # the paper's path printed the reference's own metric lines
    assert "Training loss after 40 iterations" in out
    assert "Test set: Average loss" in out
    by = {r["phase"]: r for r in results}
    assert "data_backend=" in by["train.vgg"]["detail"]
    assert "mesh=8" in by["train.ladder"]["detail"]
    assert "gmm_mosaic=interpreted" in by["train.lfm2"]["detail"]
    assert "interpret=True" in by["kernels"]["detail"]
    assert "fallbacks=[]" in by["serve"]["detail"]
    for r in results:  # compile time is reported apart from run time
        assert r["compile_s"] > 0 and r["run_s"] >= 0


def test_a_failed_phase_is_reported_not_raised(tmp_path, monkeypatch):
    import chip_smoke

    def boom(sizes, workdir, rehearse):
        raise RuntimeError("no such kernel")

    monkeypatch.setattr(chip_smoke, "PHASES", (("kernels", boom),))
    (res,) = chip_smoke.run_phases(chip_smoke.FULL, str(tmp_path),
                                   rehearse=True)
    assert res["ok"] is False and "no such kernel" in res["detail"]


def test_exits_nonzero_and_prints_no_result_without_a_tpu(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout  # it says what JAX found...
    for line in proc.stdout.splitlines():  # ...and reports no result
        assert not line.lstrip().startswith("{"), line
    assert '"ok"' not in proc.stdout
