"""Compiled-program caching.

Persistent-cache helper (tpudp/utils/compile_cache.py): must (a) no-op on
the CPU backend — the suite's platform — so smoke runs never see XLA:CPU's
per-hit AOT mismatch noise, (b) leave the directory to JAX whenever
``JAX_COMPILATION_CACHE_DIR`` is set — no ``jax_compilation_cache_dir``
update in code — and otherwise use the one fixed in-checkout path, and
(c) actually zero the thresholds (a silently renamed config flag in a JAX
upgrade would otherwise disable caching without any signal; failures
propagate).

Step-program sharing: the serve engine builds its programs once per model
CONFIG (weights are arguments), so engines over one config share them.
"""

import os

import jax
import pytest

from tpudp.utils.compile_cache import DEFAULT_DIR, enable_persistent_cache


@pytest.fixture()
def _restore_cache_config():
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
            jax.config.jax_persistent_cache_min_entry_size_bytes)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", prev[2])


def test_noop_on_cpu_backend(monkeypatch):
    # conftest forces the CPU platform, so the resolved-backend gate trips.
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append(k))
    assert enable_persistent_cache() is None
    assert updates == []


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path,
                                _restore_cache_config):
    """JAX_COMPILATION_CACHE_DIR set => JAX reads its own variable; the
    helper sets the two thresholds and NEVER updates
    jax_compilation_cache_dir (an in-code update would override where the
    machine's owner placed the cache)."""
    d = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    updates = {}
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.__setitem__(k, v), real_update(k, v)))
    assert enable_persistent_cache(force=True) == d
    assert "jax_compilation_cache_dir" not in updates
    assert updates == {"jax_persistent_cache_min_compile_time_secs": 0.0,
                       "jax_persistent_cache_min_entry_size_bytes": 0}
    assert not os.path.exists(d)  # nothing created on JAX's behalf


def test_unset_env_uses_the_fixed_checkout_path(monkeypatch,
                                                _restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_persistent_cache(force=True) == DEFAULT_DIR
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_DIR == os.path.join(repo, "bench_results", "xla_cache")
    assert os.path.isdir(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_engines_share_step_programs():
    """Engines over one model CONFIG reuse one set of step programs
    (the weights are arguments) — the multi-engine deployment pattern
    and the reason a preemption/churn storm can never recompile — even
    when they serve different weight trees."""
    import numpy as np

    from tpudp.models.gpt2 import GPT2, GPT2Config
    from tpudp.serve import Engine

    cfg = GPT2Config(vocab_size=32, max_seq_len=32, num_layers=1,
                     num_heads=2, d_model=16)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32), train=False)["params"]
    other = jax.tree.map(lambda a: a + 1, params)
    e1 = Engine(model, params, num_slots=2, prefill_chunk=8)
    e2 = Engine(model, other, num_slots=4, prefill_chunk=8)
    ms1, ms2 = e1._mstates[None], e2._mstates[None]
    assert ms1.decode_step.func is ms2.decode_step.func
    assert ms1.prefill_step.func is ms2.prefill_step.func
    # each engine's programs are bound to ITS weights
    assert ms1.decode_step.args[0] is params
    assert ms2.decode_step.args[0] is other
