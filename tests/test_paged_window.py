"""The window mask of the paged attention paths (tpudp/ops/
paged_attention.py, ``window=``): the einsum path and the two interpreted
kernels against a dense masked softmax across page boundaries, with the
entries behind the window mapped or freed; and ``window=None`` tracing
exactly the parent's program for GPT-2 and LLaMA."""

import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.ops.paged_attention import paged_attention

gen = importlib.import_module("tpudp.models.generate")


def _dense_window_attention(q, k, v, q_pos, window):
    """``q`` ``(n, h, dh)`` at ``q_pos`` ``(n,)`` over the whole sequence
    ``k``, ``v`` ``(t, kv, dh)``: a dense masked softmax in float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    g = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, g, axis=1), np.repeat(v, g, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    ahead = np.asarray(q_pos)[:, None] - np.arange(k.shape[0])[None, :]
    s = np.where((ahead >= 0) & (ahead < window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("freed", [False, True, "ragged"],
                         ids=["mapped", "freed", "ragged"])
@pytest.mark.parametrize("impl", ["einsum", "kernel"])
@pytest.mark.parametrize("shape", ["decode", "chunk", "verify"])
def test_windowed_paged_attention_is_a_dense_masked_softmax(shape, impl,
                                                            freed):
    """Across page boundaries, pages in scattered order, with the table
    entries behind the window still mapped or freed (``-1``): the mask
    alone decides what a sliding layer sees.  ``ragged``: one slot's
    leading entries freed and another's still mapped, and in a decode run
    a third slot, mapped, that is not in it (the decode kernel reads no
    page for it: its row is zero)."""
    t_page, window, kv, h, dh = 8, 12, 2, 8, 16
    rng = np.random.default_rng(0)
    k = rng.normal(size=(48, kv, dh)).astype(np.float32)
    v = rng.normal(size=(48, kv, dh)).astype(np.float32)
    order = [5, 0, 3, 6, 1, 4]  # logical page j lives in pool page order[j]
    pool_k = np.zeros((8, t_page, kv * dh), np.float32)
    pool_v = np.zeros_like(pool_k)
    for j, page in enumerate(order):
        pool_k[page] = k[j * t_page:(j + 1) * t_page].reshape(t_page, -1)
        pool_v[page] = v[j * t_page:(j + 1) * t_page].reshape(t_page, -1)
    pool_k[7] = pool_v[7] = 1e3  # the scratch page: finite garbage
    if shape == "decode":  # two slots at their own depths
        pos, cur = np.array([37, 9], np.int32), 1
    elif shape == "chunk":  # one page-aligned chunk, a scalar depth
        pos, cur = np.int32(32), t_page
    else:  # a window of three positions a slot
        pos, cur = np.array([21, 38], np.int32), 3
    active = None
    if freed == "ragged" and shape == "decode":
        pos, active = np.append(pos, 30).astype(np.int32), [True] * 2 + [False]
    starts = np.atleast_1d(pos)
    table = np.tile(np.asarray(order, np.int32), (starts.size, 1))
    if freed:
        for i, (row, start) in enumerate(zip(table, starts)):
            if freed is True or i == 0:
                row[:max(start - window + 1, 0) // t_page] = -1
    q = rng.normal(size=(starts.size, cur, h, dh)).astype(np.float32)
    got = paged_attention(
        jnp.asarray(q), (jnp.asarray(pool_k), jnp.asarray(pool_v)),
        jnp.asarray(table), jnp.asarray(pos), dtype=jnp.float32,
        grouped=True, impl=impl, window=window,
        active=None if active is None else jnp.asarray(active))
    for b, start in enumerate(starts):
        if active is not None and not active[b]:
            if impl == "kernel":
                np.testing.assert_array_equal(got[b], 0)
            continue
        want = _dense_window_attention(q[b], k, v, start + np.arange(cur),
                                       window)
        np.testing.assert_allclose(got[b], want, atol=2e-5)


# what `audit.fingerprint` gave at the parent commit (4caa61f) for the
# GPT-2 and LLaMA paged forwards below: `window` unset must trace exactly
# the causal program.  A PR that MEANS to change these programs' traces
# replaces the values (tools/trace_lock.json pins the engine's programs
# the same way).
PARENT_TRACES = {
    "gpt2.decode.einsum":
        "d9791d1b63aed26b79dee6af5f08cbb7c763f28b4cc6199238ea70557804556b",
    "gpt2.prefill.einsum":
        "38fdaf23711091aa90c83e01a78f1abcd901dd5d44d9e15da7b9a8ee905bf2d9",
    "gpt2.decode.kernel":  # the decode kernel's grid is its visit list
        "7630acaf226133f8ef76e6a0b7306277d170fe311712d56dbfbf56d2e2189ca5",
    "gpt2.prefill.kernel":
        "93ca3ecadd6d32c5e86fe93dbc873c882a6186d0fa14ef4654ce783ba9d58236",
    "llama.decode.einsum":
        "03b316cd23f4ffe2be72df46fa12e484589dd0bec576c0ca628bcdb98bc565ec",
    "llama.prefill.einsum":
        "6dd9c311c846f377336b32271d6fd006f690b4f08902e9ea596e153db0464c1c",
    "llama.decode.kernel":
        "f9bf1306188d5e841fe2669a5a3fc36ad71ba423fe9fbd27bf0eb8b96599c420",
    "llama.prefill.kernel":
        "b29b05b5fdc440fa08c23f610a02a0d2f148e2822b4936de6d780798b81ec0f2"}


@pytest.mark.parametrize("case", list(PARENT_TRACES))
def test_window_none_traces_the_parents_program(case):
    from tpudp.analysis import audit
    from tpudp.models.gpt2 import GPT2, GPT2Config
    from tpudp.models.llama import Llama, LlamaConfig

    family, step, impl = case.split(".")
    if family == "gpt2":
        cfg = GPT2Config(vocab_size=64, max_seq_len=64, num_layers=2,
                         num_heads=2, d_model=32)
        model = GPT2(cfg)
    else:
        cfg = LlamaConfig(vocab_size=64, max_seq_len=64, num_layers=2,
                          num_heads=4, num_kv_heads=2, d_model=32)
        model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    pool = gen.page_type(cfg).zeros(cfg, 7, 8)
    table = np.arange(8, dtype=np.int32).reshape(2, 4) % 6

    def fwd(p, pool, t, tok, pos, act):
        return gen._forward_paged(cfg, p, tok, pool, t, pos, act, impl)

    args = ((params, pool, table, np.zeros((2, 1), np.int32),
             np.array([3, 9], np.int32), np.ones(2, bool))
            if step == "decode" else
            (params, pool, table[:1], np.zeros((1, 8), np.int32),
             np.int32(8), np.ones(1, bool)))
    assert audit.fingerprint(fwd, args)["fingerprint"] == PARENT_TRACES[case]


def test_the_other_families_lock_entries_did_not_move():
    """The engine's registered programs of GPT-2 and the latent family
    keep the fingerprints the lockfile had when the window family came,
    but for the programs' counter accumulator, since removed, the
    prefill programs' first-token tail, since added, and the decode
    kernel's grid, since a visit list (the audit test holds the tree to
    the lockfile)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "tools", "trace_lock.json")) as f:
        programs = json.load(f)["programs"]
    parent = {
        "serve.decode_paged@s2m32p6": "d2d61685bec1c5e8",
        "serve.decode_paged_kernel@s2m32p6": "a465f65edb911bf9",
        "serve.prefill_paged@s2m32p6c8": "74dc2f514dfb93bc",
        "serve.prefill_paged_kernel@s2m32p6c8": "e7e554c0883b4c62",
        "serve.verify_paged_kernel@s2m32p6k3": "175e06f932497d8e",
        "serve.decode_paged_latent@s2m32p6": "6c5086a9a37c29b9",
        "serve.prefill_paged_latent@s2m32p6c8": "b3d1810e1e2d13c7"}
    for name, start in parent.items():
        assert programs[name]["fingerprint"].startswith(start), name
    assert {"serve.decode_paged_windowed@s2m32p6",
            "serve.prefill_paged_windowed@s2m32p6c8"} <= set(programs)
