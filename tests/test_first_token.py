"""The first token of a request is sampled inside the prefill program that
ends its prompt: the same sample the engine drew when it was a program of
its own.  On the CPU's tiny configurations of the four serving families
(GPT-2 through the dense arena and through pages, the latent family, the
windowed family) every first token and the key chain it leaves are the
ones a reference computes from the final chunk's logits with the old
algebra (``split_keys`` on ``PRNGKey(seed)``, then ``sample_tokens``);
a slot vacated while still prefilling resumes with its pending key; the
host-made key is ``PRNGKey``'s; admission and a finished prompt enqueue
no eager operation; and no decode program's trace moved."""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from perf.harness.cells import load_module
from tpudp.models.generate import (KVCache, _forward_cached, _forward_paged,
                                   page_layout)
from tpudp.models.gpt2 import gpt2_small
from tpudp.ops.sampling import sample_tokens, split_keys
from tpudp.serve import Engine
from tpudp.serve.engine import _build_steps, _seed_key
from tpudp.serve.faults import FaultySteps
from tpudp.serve.prefix_cache import PagePool
from tpudp.serve.tenancy import TenantClass
from tpudp.train import init_state, make_optimizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = 8

# Two layers of each expert family at small widths, none a multiple of
# 128 (the expert layer runs its plain loop).
PANGU = dict(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=2, first_k_dense_replace=1,
    num_attention_heads=2, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
    num_experts_routed=8, first_expert=0, norm_topk_prob=True,
    routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=10000,
    max_position_embeddings=64, compute_dtype="float32",
    serve={"weight_dtype": "float32"})
LAGUNA = dict(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_hidden_layers=2, layer_types=["full_attention", "sliding_attention"],
    mlp_layer_types=["dense", "sparse"],
    num_attention_heads_per_layer=[4, 2], num_key_value_heads=2,
    head_dim=16, sliding_window=2 * CHUNK,
    rope_parameters={
        "full_attention": {"rope_type": "default", "rope_theta": 10000},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    num_experts=4, num_experts_per_tok=2, moe_routed_scaling_factor=2.5,
    gating=True, rms_norm_eps=1e-6, max_position_embeddings=64,
    compute_dtype="float32", serve={"weight_dtype": "float32"})

# greedy, temperature alone, top-k, top-p; seeds small, at the 32-bit
# edge, negative and past 32 bits
REQUESTS = [dict(temperature=0.0, seed=3),
            dict(temperature=0.8, seed=2**31 - 1),
            dict(temperature=0.9, top_k=5, seed=-7),
            dict(temperature=1.1, top_p=0.7, seed=12345678901)]
LENGTHS = (5, 19)   # one chunk; three chunks, the last one partial


def _family(name):
    """(model, params, engine options) of one serving family."""
    if name in ("dense", "paged"):
        model = gpt2_small(vocab_size=61, max_seq_len=64, num_layers=2,
                           num_heads=2, d_model=32)
        params = init_state(model, make_optimizer(),
                            input_shape=(1, 8)).params
        return model, params, ({} if name == "dense" else {"kv_pages": 16})
    fam = load_module("families",
                      "pangu_moe" if name == "latent" else "laguna_moe")
    model = fam.build_model(PANGU if name == "latent" else LAGUNA)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, {"kv_pages": 16}


@pytest.fixture(scope="module", params=["dense", "paged", "latent",
                                        "windowed"])
def family(request):
    return _family(request.param)


def _engine(model, params, opts, **kw):
    return Engine(model, params, **{"num_slots": 1, "max_len": 48,
                                    "prefill_chunk": CHUNK, **opts, **kw})


@jax.jit
def _old_sample(logits, temp, top_k, top_p, key):
    """The first-token sample as the engine made it in a program of its
    own: split the slot's key once, sample the row with the subkey."""
    carry, sub = split_keys(key[None])
    tok = sample_tokens(logits, temp[None], top_k[None], top_p[None], sub)
    return tok[0], carry[0]


def _final_logits(eng, prompt):
    """The prompt's last row of logits as the engine's chunks compute
    them: the family's cached forward as its prefill program calls it,
    chunk by chunk over a fresh cache of the engine's geometry."""
    ms = eng._mstates[None]
    cfg, c = ms.config, eng.prefill_chunk
    layout = page_layout(cfg) if eng._paged else "dense"
    if layout == "dense":
        state = KVCache.zeros(cfg, 1, eng.max_len)
    else:
        pages = eng._max_pages
        state = PagePool(cfg, pages, c, None, window_pages=(
            pages if layout == "windowed" else 0)).pages
        row = np.arange(pages, dtype=np.int32)[None]
        table = (row, row) if layout == "windowed" else row

    @jax.jit
    def chunk(params, state, tokens, pos, last):
        if layout == "dense":
            logits, state = _forward_cached(cfg, params, tokens, state, pos)
        elif layout in ("latent", "windowed"):
            logits, state = _forward_paged(
                cfg, params, tokens, state, table, pos, jnp.ones((1,), bool),
                eng.paged_attn, last=last, routed=[])
            return logits[:, 0], state
        else:
            logits, state = _forward_paged(
                cfg, params, tokens, state, table, pos, jnp.ones((1,), bool),
                impl=eng.paged_attn)
        return lax.dynamic_index_in_dim(logits, last, axis=1,
                                        keepdims=False), state

    for start in range(0, prompt.size, c):
        end = min(start + c, prompt.size)
        buf = np.zeros((1, c), np.int32)
        buf[0, :end - start] = prompt[start:end]
        logits, state = chunk(ms.params, state, buf, np.int32(start),
                              np.int32(end - start - 1))
    return logits


def _reference(eng, prompt, temperature=0.0, top_k=0, top_p=1.0, seed=0):
    """(first token, key chain after it) by the old algebra."""
    tok, carry = _old_sample(
        _final_logits(eng, prompt), np.float32(temperature),
        np.int32(top_k), np.float32(top_p), jax.random.PRNGKey(seed))
    return int(tok), np.asarray(carry)


def _prompts(vocab):
    rng = np.random.default_rng(11)
    return [rng.integers(0, vocab, size=n, dtype=np.int32) for n in LENGTHS]


def test_the_first_token_and_its_key_chain_are_the_old_samples(family):
    """One request at a time, each to its first token: the token and the
    slot's key chain equal the reference's, for every kind of request
    and both prompt lengths."""
    model, params, opts = family
    eng = _engine(model, params, opts)
    for prompt in _prompts(model.config.vocab_size):
        for req in REQUESTS:
            h = eng.submit(prompt, 1, **req)
            eng.run_until_complete()
            tok, carry = _reference(eng, prompt, **req)
            assert h.tokens == [tok], (prompt.size, req)
            np.testing.assert_array_equal(np.asarray(eng._keys[0]), carry)
    st = eng.metrics()["stats"]
    assert st["first_tokens_in_prefill"] == 2 * len(REQUESTS)
    assert st["eager_key_updates"] == 0


def test_co_resident_requests_sample_their_own_first_tokens(family):
    """All eight requests served together on two slots, prompts
    prefilling while others decode: every first token is still the
    reference's, and each stream equals the same request served alone."""
    model, params, opts = family
    eng = _engine(model, params, opts, num_slots=2)
    work = [(p, req) for p in _prompts(model.config.vocab_size)
            for req in REQUESTS]
    handles = [eng.submit(p, 4, **req) for p, req in work]
    eng.run_until_complete()
    alone = _engine(model, params, opts)
    for (prompt, req), h in zip(work, handles):
        assert h.ok and h.tokens[0] == _reference(eng, prompt, **req)[0]
        solo = alone.submit(prompt, 4, **req)
        alone.run_until_complete()
        assert solo.tokens == h.tokens, (prompt.size, req)


def _served_alone(model, params, opts, prompt, req, n, **kw):
    eng = _engine(model, params, opts, **kw)
    h = eng.submit(prompt, n, **req)
    eng.run_until_complete()
    return h.tokens


@pytest.mark.parametrize("name", ["dense", "paged"])
def test_a_slot_preempted_while_prefilling_resumes_with_its_pending_key(
        name):
    """A sampled low-tier request evicted after its first chunk, before
    any token: its saved key is the host's pending key, and it finishes
    with the tokens of an uninterrupted run."""
    model, params, opts = _family(name)
    tenants = {"high": TenantClass(priority=1),
               "low": TenantClass(priority=0)}
    prompt = _prompts(model.config.vocab_size)[1]
    req = REQUESTS[2]
    eng = _engine(model, params, opts, tenants=tenants)
    low = eng.submit(prompt, 6, tenant="low", **req)
    eng.step()   # admitted, its first chunk prefilled
    assert low._slot == 0 and not low.tokens
    high = eng.submit(prompt[:5], 3, tenant="high")
    eng.run_until_complete()
    assert low.preemptions == 1 and low.ok and high.ok
    np.testing.assert_array_equal(low._resume_key, _seed_key(req["seed"]))
    assert low.tokens == _served_alone(model, params, opts, prompt,
                                       {**req, "tenant": "low"}, 6,
                                       tenants=tenants)


@pytest.mark.parametrize("name", ["dense", "paged"])
def test_a_slot_requeued_while_prefilling_resumes_with_its_pending_key(
        name):
    """A step failure at a sampled request's second prefill chunk: the
    containment vacates the slot with its pending key, and the retried
    request emits the tokens of an uninterrupted run."""
    model, params, opts = _family(name)
    prompt = _prompts(model.config.vocab_size)[1]
    req = REQUESTS[3]
    eng = _engine(model, params, opts)
    eng.step_fault_hook = FaultySteps(fail_at={eng._device_calls + 1},
                                      kind="prefill")
    h = eng.submit(prompt, 6, **req)
    eng.run_until_complete()
    assert eng.stats["requeued"] == 1 and h.ok
    assert h.tokens == _served_alone(model, params, opts, prompt, req, 6)


def test_a_ticket_exported_while_prefilling_carries_the_pending_key():
    """A request migrated after its first chunk carries the initial key
    in its ticket and finishes on the other engine with the tokens of an
    uninterrupted run."""
    model, params, opts = _family("paged")
    prompt = _prompts(model.config.vocab_size)[1]
    req = REQUESTS[1]
    src, dst = (_engine(model, params, opts) for _ in range(2))
    h = src.submit(prompt, 6, **req)
    src.step()
    ticket = src.export_ticket(h)
    np.testing.assert_array_equal(ticket.resume_key, _seed_key(req["seed"]))
    moved = dst.admit_ticket(ticket)
    dst.run_until_complete()
    assert moved.tokens == _served_alone(model, params, opts, prompt, req, 6)


def test_admission_and_a_finished_prompt_enqueue_no_eager_operation():
    """Over a served run of greedy and sampled requests the engine calls
    ``_enqueued`` (the hook of every eager device operation) with no
    array, runs no ``sample`` program, and counts one first token in a
    prefill program per request; only a fused replay updates the chain
    eagerly."""
    model, params, opts = _family("paged")
    prompts = _prompts(model.config.vocab_size)
    eng = _engine(model, params, opts, num_slots=2)
    seen = []
    hook = eng._enqueued
    eng._enqueued = lambda out: (seen.append(out), hook(out))[1]
    for p, req in zip(prompts * 2, REQUESTS):
        eng.submit(p, 5, **req)
    eng.run_until_complete()
    assert not [out for out in seen if out is not None]
    m = eng.metrics()
    assert m["stats"]["first_tokens_in_prefill"] == len(REQUESTS)
    assert m["stats"]["eager_key_updates"] == 0
    assert m["spans"]["prefill"]["count"] == m["stats"]["prefill_chunks"]
    assert not {"sample", "keys"} & set(m["spans"])
    fused = _engine(model, params, opts, num_slots=2, decode_fuse=4)
    fused.generate_many(prompts, 6)
    assert fused.metrics()["stats"]["eager_key_updates"] > 0


@pytest.mark.parametrize("seed", [0, 1, 2**31 - 1, 2**31, 2**32 + 7,
                                  2**40 + 5, 2**63 - 1, -1, -7, -2**31,
                                  -2**40 - 3, -2**63])
@pytest.mark.parametrize("x64", [False, True])
def test_the_host_made_key_is_prngkey(seed, x64):
    """Admission's key, made on the host, is ``PRNGKey(seed)`` bit for
    bit for every seed ``submit`` accepts, with 64-bit types off and on;
    past 64 bits both refuse alike."""
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.PRNGKey(seed))
        got = _seed_key(seed)
        assert got.dtype == want.dtype == np.uint32
        np.testing.assert_array_equal(got, want)
        for huge in (2**63, -2**63 - 1):
            with pytest.raises(OverflowError):
                jax.random.PRNGKey(huge)
            with pytest.raises(OverflowError):
                _seed_key(huge)


#: The fingerprints every serve program but the prefill programs had in
#: the lockfile before the first-token sample moved into the prefill
#: programs: none of them changed.  (The two paged-decode kernel programs
#: have moved since, when the kernel's grid became its visit list.)
UNMOVED = {
    "serve.decode_paged@s2m32p6": "d2d61685bec1c5e8",
    "serve.decode_paged_kernel@s2m32p6": "a465f65edb911bf9",
    "serve.decode_paged_latent@s2m32p6": "6c5086a9a37c29b9",
    "serve.decode_paged_latent_kernel@s2m32p6": "6f67a2220118f3e4",
    "serve.decode_paged_windowed@s2m32p6": "67c337fd1254e9ea",
    "serve.decode_step@s2m32": "92869d8c11129d0b",
    "serve.draft_model@ctx16k3": "c6afc03209fb53b7",
    "serve.fused_decode@s2m32n4": "8dad0f5d988a4e21",
    "serve.fused_decode_paged@s2m32p6n4": "f3430b804c0d2658",
    "serve.fused_decode_paged_kernel@s2m32p6n4": "f6323f0f8608ed96",
    "serve.fused_decode_paged_stream@s2m32p6n4": "e9b36befc45ec9ab",
    "serve.fused_decode_stream@s2m32n4": "3ff1fab74a1a9be1",
    "serve.fused_spec_decode@s2m32k3n4": "a581c1c962a8229c",
    "serve.fused_spec_decode_stream@s2m32k3n4": "8e5ab1d0f33468b6",
    "serve.fused_spec_paged@s2m32p6k3n4": "3bb1731d7d7b32ba",
    "serve.fused_spec_paged_kernel@s2m32p6k3n4": "b11d40ca11fb38b7",
    "serve.fused_spec_paged_stream@s2m32p6k3n4": "185dc941af8c6bdd",
    "serve.tree_verify@s2m32t5": "36c25b31cbcef227",
    "serve.tree_verify_paged@s2m32p6t5": "1ebb4b7fa399d603",
    "serve.tree_verify_paged_kernel@s2m32p6t5": "1598f12ec2b89e9a",
    "serve.verify_paged@s2m32p6k3": "2d06489eb381a58b",
    "serve.verify_paged_kernel@s2m32p6k3": "175e06f932497d8e",
    "serve.verify_step@s2m32k3": "347fcf398d352cd0",
}


def test_no_decode_program_moved_and_the_sample_program_is_gone():
    """Every serve program of the lockfile but the prefill programs keeps
    its fingerprint; ``serve.sample_row`` is no longer registered; and no
    prefill program takes more host arrays than it did (the row, the
    tokens and one packed array, where there were the row or slot, the
    tokens, start and last)."""
    with open(os.path.join(ROOT, "tools", "trace_lock.json")) as f:
        programs = json.load(f)["programs"]
    for name, start in UNMOVED.items():
        assert programs[name]["fingerprint"].startswith(start), name
    serve = {n for n in programs if n.startswith("serve.")}
    # the power-retention family's decode programs came after this test
    # (its prefill program is among the prefill programs)
    later = {"serve.decode_retention@s2m32",
             "serve.decode_retention_kernel@s2m32"}
    assert serve == set(UNMOVED) | later | {n for n in serve
                                            if ".prefill_" in n}
    assert not any("sample_row" in n for n in programs)
    configs = [_family(n)[0].config for n in ("dense", "latent", "windowed")]
    steps = [s for cfg in configs for impl in ("einsum", "kernel")
             for s in _build_steps(cfg, impl)[2::6] if s is not None]
    assert len(steps) == 8
    for fn in steps:
        names = list(inspect.signature(fn).parameters)
        host = [n for n in names if n not in ("params", "cache", "pool",
                                              "keys")]
        assert names[-1] == "keys" and "meta" in host and len(host) <= 3, (
            names)
