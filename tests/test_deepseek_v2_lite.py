"""DeepSeek-V2-Lite at the repository's smoke size (one dense and two MoE
layers, 4 of 8 routed experts held, top-2, one shared expert) against
the benchmark's plain float32 reference (`bench/reference/
deepseek_v2_lite.py`) on the benchmark's seeded weights
(`bench/weights_moe.py`): prefill then decode through the latent cache,
the dropless expert layer against the dense oracle where the capacity
form drops, the gates, the expert shares against the uncut layer, YaRN,
and the serving engine's two-stack cache and routing counts."""
from __future__ import annotations

import copy
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import weights_moe  # noqa: E402
from bench.drivers import serve_moe  # noqa: E402
from bench.reference import deepseek_v2_lite as ref  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.models import Model, moe  # noqa: E402
from repro.models.layers import yarn_frequencies  # noqa: E402

SEED = 2**31 + 7
PROMPT, DECODE = 40, 6
SMOKE = get_smoke_config("deepseek-v2-lite")


def model_dict(cfg=SMOKE) -> dict:
    """The benchmark's published model keys for `cfg`, with the share's
    router width and offset."""
    config = json.loads((ROOT / "bench" / "configs" /
                         "deepseek-v2-lite.json").read_text())
    m = copy.deepcopy(serve_moe.model_dict(config))
    m.update(hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
             num_attention_heads=cfg.num_heads,
             num_key_value_heads=cfg.num_kv_heads,
             intermediate_size=cfg.d_ff, moe_intermediate_size=cfg.expert_d_ff,
             n_routed_experts=cfg.num_experts,
             num_experts_per_tok=cfg.experts_per_token,
             n_shared_experts=cfg.shared_experts, vocab_size=cfg.vocab_size,
             kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_dim,
             qk_rope_head_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
             norm_topk_prob=cfg.moe_renormalize,
             routed_scaling_factor=cfg.moe_gate_scale,
             router_experts=cfg.router_experts,
             expert_offset=cfg.expert_offset)
    return m


def program(dtype: str = "float32"):
    """(model, params) at the smoke size in `dtype`, on the seeded
    weights (the router stays float32)."""
    cfg = dataclasses.replace(SMOKE, dtype=dtype)
    params = weights_moe.program_params(
        jax.random.key(SEED), model_dict(), SMOKE.padded_vocab,
        Model(SMOKE).abstract_params())
    params = jax.tree.map(
        lambda x: x if x.dtype == jnp.float32 else x.astype(dtype), params)
    return Model(cfg), params


def _prefill_then_decode(dtype: str):
    model, params = program(dtype)
    seq = np.random.default_rng(0).integers(0, SMOKE.vocab_size,
                                            PROMPT + DECODE)
    logits, cache = model.prefill(params, jnp.asarray(seq[None, :PROMPT]),
                                  None, max_len=PROMPT + DECODE)
    out = [logits[0, -1, :SMOKE.vocab_size]]
    for t in seq[PROMPT:PROMPT + DECODE - 1]:
        logits, cache = model.decode_step(params, cache,
                                          jnp.asarray([[t]], jnp.int32))
        out.append(logits[0, -1, :SMOKE.vocab_size])
    got = np.stack([np.asarray(x, np.float32) for x in out])
    want = np.asarray(ref.logits(SEED, model_dict(), SMOKE.padded_vocab,
                                 seq[:-1],
                                 np.arange(PROMPT - 1, PROMPT + DECODE - 1)))
    return got, want


def test_float32_program_matches_reference():
    # Same equations in the same precision, over the same held experts:
    # only the order of float32 sums differs (the absorbed decode
    # attention, the grouped matmul, the blocked reference attention).
    # The largest difference read 6.3e-7 against logits up to 1.21 on the
    # host CPU; 1e-4 leaves two orders of room and still fails any
    # departure in the equations (a dropped shared expert, the wrong gate
    # scale or mscale).
    got, want = _prefill_then_decode("float32")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


BF16_BOUND = 0.03


def test_bfloat16_program_tracks_reference():
    # The served precision rounds weights, activations and the cache to
    # 8 bits of mantissa: it read 0.0203 relative L2 against the float32
    # reference here, with the greedy choice unchanged at every position;
    # the float8 control read 0.048 over the same positions. 0.03 sits
    # near the geometric middle of the two.
    got, want = _prefill_then_decode("bfloat16")
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < BF16_BOUND, err
    assert (got.argmax(-1) == want.argmax(-1)).mean() >= 0.8


def test_fp8_control_moves_logits():
    # float8 matrices read 0.051 relative L2 here and fail the bound the
    # bfloat16 program keeps.
    m = model_dict()
    seq = np.random.default_rng(1).integers(0, m["vocab_size"], 64)
    read = np.arange(32, 64)
    exact = np.asarray(ref.logits(SEED, m, SMOKE.padded_vocab, seq, read))
    fp8 = np.asarray(ref.logits(SEED, m, SMOKE.padded_vocab, seq, read,
                                fp8=True))
    assert np.linalg.norm(fp8 - exact) / np.linalg.norm(exact) > BF16_BOUND


# ------------------------------ expert layer ---------------------------- #
def _layer(m: dict, index: int = 1):
    """MoE layer `index`'s weights outside the routed experts (float32)
    and a batch of normalised inputs."""
    w = weights_moe.layer(jax.random.key(SEED), index, m, dense=False)
    x = jax.random.normal(jax.random.key(3), (2, 24, m["hidden_size"]))
    return w, x


def _program_params(w, m, index: int = 1) -> dict:
    ex = weights_moe.held_experts(jax.random.key(SEED), index, m)
    return {"router": w["router"], **ex,
            "shared": {"w_gate": w["shared_gate"], "w_up": w["shared_up"],
                       "w_down": w["shared_down"]}}


def _cfg(m: dict, **kw):
    return dataclasses.replace(
        SMOKE, num_experts=m["n_routed_experts"],
        expert_offset=m["expert_offset"], dtype="float32", **kw)


@pytest.mark.parametrize("held", [(0, 8), (4, 4)])
def test_dropless_equals_dense_oracle_where_capacity_drops(held):
    """Routing skewed towards two experts: the capacity form (1.25 x the
    mean load) drops tokens, the dropless layer equals the oracle that
    computes every held expert densely, for the whole expert set and for
    a share of it."""
    m = dict(model_dict(), expert_offset=held[0], n_routed_experts=held[1])
    w, x = _layer(m)
    p = _program_params(w, m)
    p["router"] = p["router"].at[:, 4:6].add(0.05)      # skew to 4 and 5
    cfg = _cfg(m)
    y, counts = moe.moe_ffn_dropless(x, p, cfg)
    want, _ = moe.moe_ffn_dense_reference(x, p, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    capped, _ = moe.moe_ffn(x, p, cfg)
    assert float(jnp.max(jnp.abs(capped - want))) > 1e-3   # it dropped
    # counts: every held (token, choice) slot, and the groups that have one
    _, idx = jax.lax.top_k(moe.router_probs(x, p["router"]), 2)
    local = np.asarray(idx).ravel() - held[0]
    local = local[(local >= 0) & (local < held[1])]
    tokens = np.bincount(local, minlength=held[1])
    np.testing.assert_array_equal(np.asarray(counts["tokens"]), tokens)
    assert int(counts["groups"]) == int((tokens > 0).sum())


@pytest.mark.parametrize("norm,scale", [(False, 1.0), (False, 2.5),
                                        (True, 1.0)])
def test_gates_match_reference(norm, scale):
    """Softmax over every routed expert in float32, greedy top-k, then
    renormalisation only where configured, times the scaling factor."""
    m = dict(model_dict(), norm_topk_prob=norm, routed_scaling_factor=scale)
    w, x = _layer(m)
    xt = x.reshape(-1, x.shape[-1])
    cfg = _cfg(m, moe_renormalize=norm, moe_gate_scale=scale)
    gate, idx = jax.lax.top_k(moe.router_probs(xt, w["router"]), 2)
    gate = moe.gate_weights(gate, cfg)
    want_idx, want_gate = ref.gates(xt, w["router"], m)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(gate), np.asarray(want_gate),
                               rtol=1e-6, atol=1e-7)
    sums = np.asarray(gate).sum(-1)
    if norm:
        np.testing.assert_allclose(sums, scale, rtol=1e-6)
    else:
        assert (sums < scale).all()     # the unchosen experts keep the rest


def test_shares_add_up_to_the_uncut_layer():
    """The four 2-expert shares of the 8 routed experts, each computed by
    the program's dropless layer for the experts it holds, plus the
    shared expert counted once, equal the reference's uncut layer."""
    full = dict(model_dict(), expert_offset=0, n_routed_experts=8)
    w, x = _layer(full)
    xt = x.reshape(-1, x.shape[-1])
    total = 0.0
    for offset in range(0, 8, 2):
        m = dict(full, expert_offset=offset, n_routed_experts=2)
        y, _ = moe.moe_ffn_dropless(x, _program_params(w, m), _cfg(m))
        total = total + y
    total = total + moe.shared_ffn(x, _program_params(w, full))
    idx, g = ref.gates(xt, w["router"], full)
    with jax.default_matmul_precision("highest"):
        want = ref.routed(xt, idx, g, lambda e: weights_moe.expert(
            jax.random.key(SEED), 1, e, full), full) + ref.shared(xt, w)
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape),
                               np.asarray(want), rtol=1e-4, atol=1e-5)


def test_yarn_matches_reference_at_published_widths():
    """Frequencies and softmax scale of the published config: the rope
    dimensions' correction range is 10-23 of 64; scale 192^-0.5 x 1.590."""
    cfg = get_config("deepseek-v2-lite")
    m = serve_moe.model_dict(json.loads(
        (ROOT / "bench" / "configs" / "deepseek-v2-lite.json").read_text()))
    inv = yarn_frequencies(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn_factor,
                           cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                           cfg.yarn_beta_slow)
    want_inv, cs, scale = ref.yarn(m)
    np.testing.assert_allclose(inv, np.asarray(want_inv), rtol=1e-6)
    assert cs == 1.0
    assert cfg.attn_scale == pytest.approx(scale, rel=1e-12)
    assert cfg.attn_scale * 192 ** 0.5 == pytest.approx(1.5896, abs=1e-4)
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)


# -------------------------------- engine -------------------------------- #
def _host_counts(requests, m, prompts):
    """Routing counts of the held experts from the reference's routing:
    tokens per held expert and non-empty (step, layer, held expert)
    groups, prefill (one step a request) and decode (steps batched over
    the requests still running) apart."""
    lo, n = m["expert_offset"], m["n_routed_experts"]
    tokens = np.zeros((2, n), np.int64)
    groups = np.zeros(2, np.int64)
    decode_sets = {}                  # (step, layer) -> held experts hit
    for prompt, out in zip(prompts, requests):
        seq = list(prompt) + list(out[:-1])
        idx = np.asarray(ref.routing(SEED, m, SMOKE.padded_vocab, seq))
        local = idx - lo                                  # (layers, S, k)
        held = (local >= 0) & (local < n)
        p = len(prompt)
        for phase, sl in ((0, slice(0, p)), (1, slice(p, len(seq)))):
            tokens[phase] += np.bincount(local[:, sl][held[:, sl]],
                                         minlength=n)
        for layer in range(idx.shape[0]):
            groups[0] += len(set(local[layer, :p][held[layer, :p]]))
            for j in range(p, len(seq)):
                hit = local[layer, j][held[layer, j]]
                decode_sets.setdefault((j - p, layer), set()).update(hit)
    groups[1] = sum(len(v) for v in decode_sets.values())
    return tokens, groups


def test_engine_serves_two_stacks_and_counts_routing():
    from repro.serving.engine import InferenceEngine
    from repro.telemetry import TelemetryHub

    model, params = program("float32")
    hub = TelemetryHub()
    eng = InferenceEngine(model, params, max_batch=4, max_len=64,
                          policy="linux", num_host_cores=4, telemetry=hub)
    assert eng.cache["dense_latent"].shape == (1, 4, 64, 40)
    assert eng.cache["latent"].shape == (2, 4, 64, 40)
    assert eng.moe_counts()["tokens"].shape == (2, 4)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, SMOKE.vocab_size, n).tolist()
               for n in (12, 20, 9)]
    reqs = []
    for prompt, new in zip(prompts, (6, 3, 5)):
        eng.submit(prompt, new)
        reqs.append(eng.pending[-1])
    eng.step()                      # admits all three: three splices
    admitted = eng.moe_counts()
    eng.run_until_drained()
    counts = eng.moe_counts()
    # each splice kept the counts the prefills added, and decode steps
    # add to the decode row only
    np.testing.assert_array_equal(admitted["tokens"][0], counts["tokens"][0])
    assert admitted["groups"][0] == counts["groups"][0]
    tokens, groups = _host_counts([r.output for r in reqs], model_dict(),
                                  prompts)
    np.testing.assert_array_equal(counts["tokens"], tokens)
    np.testing.assert_array_equal(counts["groups"], groups)
    assert hub.timelines["engine/moe_expert_tokens"].samples()[-1][1] == \
        tuple(float(v) for v in tokens.ravel())
