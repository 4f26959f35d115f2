"""Model zoo tests: per-arch smoke (reduced configs, CPU), decode/prefill
consistency, SSD chunked-vs-naive oracle, MoE dispatch oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_arch_names, get_smoke_config
from repro.models import Model
from repro.models import mamba2, moe
from repro.models.attention import causal_mask, decode_attention, self_attention

B, S = 2, 32


def make_batch(cfg, key, seq=S):
    n_front = cfg.frontend_tokens if cfg.frontend != "none" else 0
    batch = {"tokens": jax.random.randint(key, (B, seq), 0, cfg.vocab_size)}
    if n_front:
        batch["embeds"] = jax.random.normal(
            key, (B, n_front, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", all_arch_names())
class TestArchSmoke:
    """Assigned-architecture smoke tests: one forward/train step on CPU,
    asserting output shapes and no NaNs (reduced same-family configs)."""

    def test_train_step(self, arch):
        cfg = get_smoke_config(arch)
        model = Model(cfg)
        key = jax.random.key(0)
        params = model.init(key)
        batch = make_batch(cfg, key)
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
        assert loss.shape == ()
        assert jnp.isfinite(loss)
        assert 2.0 < float(loss) < 12.0  # ~ln(vocab) at init
        finite = jax.tree.map(lambda g: bool(jnp.isfinite(g).all()), grads)
        assert all(jax.tree.leaves(finite))

    def test_prefill_decode_shapes(self, arch):
        cfg = get_smoke_config(arch)
        model = Model(cfg)
        key = jax.random.key(1)
        params = model.init(key)
        batch = make_batch(cfg, key)
        n_front = cfg.frontend_tokens if cfg.frontend != "none" else 0
        max_len = S + n_front + 8
        logits, cache = jax.jit(
            lambda p, t, e: model.prefill(p, t, e, max_len=max_len)
        )(params, batch["tokens"], batch.get("embeds"))
        assert logits.shape == (B, 1, cfg.padded_vocab)
        assert bool(jnp.isfinite(logits).all())
        tok = jnp.argmax(logits[:, :, :cfg.vocab_size], -1).astype(jnp.int32)
        step = jax.jit(model.decode_step)
        for _ in range(3):
            logits, cache = step(params, cache, tok)
            assert logits.shape == (B, 1, cfg.padded_vocab)
            assert bool(jnp.isfinite(logits).all())
            tok = jnp.argmax(logits[:, :, :cfg.vocab_size], -1).astype(
                jnp.int32)

    def test_decode_matches_prefill(self, arch):
        """Teacher-forcing consistency: decoding token t with the cache of
        tokens [0..t) must reproduce the full-prefill logits at t."""
        cfg = get_smoke_config(arch)
        # MoE prefill and decode share the dropless expert layer, so no
        # capacity drop can part them (see moe.py docstring)
        model = Model(cfg)
        key = jax.random.key(2)
        params = model.init(key)
        batch = make_batch(cfg, key, seq=16)
        tokens = batch["tokens"]
        embeds = batch.get("embeds")
        n_front = cfg.frontend_tokens if cfg.frontend != "none" else 0

        prefix, rest = tokens[:, :12], tokens[:, 12:]
        full_logits, _ = jax.jit(
            lambda p, t, e: model.prefill(p, t, e, max_len=16 + n_front)
        )(params, tokens, embeds)
        _, cache = jax.jit(
            lambda p, t, e: model.prefill(p, t, e, max_len=16 + n_front)
        )(params, prefix, embeds)
        step = jax.jit(model.decode_step)
        logits = None
        for i in range(rest.shape[1]):
            logits, cache = step(params, cache, rest[:, i:i + 1])
        np.testing.assert_allclose(
            np.asarray(logits[:, 0], np.float32),
            np.asarray(full_logits[:, 0], np.float32),
            rtol=0.08, atol=0.15)


class TestSSD:
    @pytest.mark.parametrize("l,chunk", [(32, 8), (64, 16), (128, 128)])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_chunked_matches_reference(self, l, chunk, dtype):
        key = jax.random.key(0)
        b, h, p, n = 2, 4, 8, 16
        ks = jax.random.split(key, 5)
        dt = jnp.dtype(dtype)
        x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32).astype(dt)
        dts = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
        a_log = jax.random.normal(ks[2], (h,)) * 0.5
        bb = jax.random.normal(ks[3], (b, l, n), jnp.float32).astype(dt)
        cc = jax.random.normal(ks[4], (b, l, n), jnp.float32).astype(dt)
        y_ref, h_ref = mamba2.ssd_reference(x, dts, a_log, bb, cc)
        y_chk, h_chk = mamba2.ssd_chunked(x, dts, a_log, bb, cc, chunk=chunk)
        tol = 2e-2 if dtype == "bfloat16" else 2e-4
        np.testing.assert_allclose(np.asarray(y_chk, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   rtol=tol, atol=tol * 5)
        np.testing.assert_allclose(np.asarray(h_chk), np.asarray(h_ref),
                                   rtol=1e-3, atol=1e-3)

    def test_decode_step_matches_scan(self):
        key = jax.random.key(1)
        b, l, h, p, n = 2, 8, 4, 8, 16
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (b, l, h, p))
        dts = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
        a_log = jax.random.normal(ks[2], (h,)) * 0.5
        bb = jax.random.normal(ks[3], (b, l, n))
        cc = jax.random.normal(ks[4], (b, l, n))
        y_ref, h_ref = mamba2.ssd_reference(x, dts, a_log, bb, cc)
        state = jnp.zeros((b, h, p, n))
        ys = []
        for t in range(l):
            y, state = mamba2.ssd_decode_step(
                state, x[:, t], dts[:, t], a_log, bb[:, t], cc[:, t])
            ys.append(y)
        np.testing.assert_allclose(np.stack(ys, 1), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(state), np.asarray(h_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_initial_state_carried(self):
        """Chunked prefill then decode == one long reference scan."""
        key = jax.random.key(2)
        b, l, h, p, n = 1, 16, 2, 4, 8
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (b, l, h, p))
        dts = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
        a_log = jnp.zeros((h,))
        bb = jax.random.normal(ks[3], (b, l, n))
        cc = jax.random.normal(ks[4], (b, l, n))
        y_all, h_all = mamba2.ssd_reference(x, dts, a_log, bb, cc)
        _, h_pre = mamba2.ssd_chunked(x[:, :12], dts[:, :12], a_log,
                                      bb[:, :12], cc[:, :12], chunk=4)
        state = h_pre
        for t in range(12, l):
            y, state = mamba2.ssd_decode_step(
                state, x[:, t], dts[:, t], a_log, bb[:, t], cc[:, t])
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_all[:, -1]),
                                   rtol=1e-4, atol=1e-4)


class TestMoE:
    def _setup(self, e=4, k=2, cf=8.0):
        cfg = dataclasses.replace(
            get_smoke_config("granite-moe-3b-a800m"),
            num_experts=e, experts_per_token=k, moe_capacity_factor=cf)
        key = jax.random.key(0)
        d, f = cfg.d_model, cfg.d_ff
        ks = jax.random.split(key, 5)
        params = {
            "router": jax.random.normal(ks[0], (d, e)) * 0.02,
            "w_gate": jax.random.normal(ks[1], (e, d, f)) * 0.02,
            "w_up": jax.random.normal(ks[2], (e, d, f)) * 0.02,
            "w_down": jax.random.normal(ks[3], (e, f, d)) * 0.02,
        }
        x = jax.random.normal(ks[4], (2, 16, d))
        return cfg, params, x

    def test_matches_dense_reference_at_high_capacity(self):
        """With capacity_factor large enough that nothing drops, the
        sorted-capacity dispatch must equal the dense oracle."""
        cfg, params, x = self._setup(cf=8.0)
        y, aux = moe.moe_ffn(x, params, cfg)
        y_ref, aux_ref = moe.moe_ffn_dense_reference(x, params, cfg)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)

    def test_capacity_drops_bounded(self):
        """At cf=1.0 some tokens may drop, but output stays finite and
        close in norm to the reference."""
        cfg, params, x = self._setup(cf=1.0)
        y, _ = moe.moe_ffn(x, params, cfg)
        assert bool(jnp.isfinite(y).all())
        y_ref, _ = moe.moe_ffn_dense_reference(x, params, cfg)
        # dropped fraction is small at init (balanced router)
        rel = float(jnp.linalg.norm(y - y_ref) / jnp.linalg.norm(y_ref))
        assert rel < 0.5

    def test_aux_loss_balanced_router_near_one(self):
        cfg, params, x = self._setup()
        probs = moe.router_probs(x, params["router"])
        _, idx = jax.lax.top_k(probs, cfg.experts_per_token)
        aux = moe.load_balance_loss(probs, idx, cfg.num_experts)
        assert 0.9 < float(aux) < 1.6  # ~1.0 when perfectly balanced

    def test_decode_single_token(self):
        cfg, params, _ = self._setup()
        x = jax.random.normal(jax.random.key(9), (4, 1, cfg.d_model))
        y, _ = moe.moe_ffn(x, params, cfg)
        y_ref, _ = moe.moe_ffn_dense_reference(x, params, cfg)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)


class TestAttentionCore:
    def test_sliding_window_mask(self):
        m = causal_mask(8, 8, window=3)
        assert bool(m[5, 5]) and bool(m[5, 4]) and bool(m[5, 3])
        assert not bool(m[5, 2])  # outside window
        assert not bool(m[2, 5])  # future

    def test_decode_matches_full_attention(self):
        key = jax.random.key(0)
        b, s, h, hkv, d = 2, 10, 4, 2, 16
        ks = jax.random.split(key, 3)
        q_all = jax.random.normal(ks[0], (b, s, h, d))
        k_all = jax.random.normal(ks[1], (b, s, hkv, d))
        v_all = jax.random.normal(ks[2], (b, s, hkv, d))
        full = self_attention(q_all, k_all, v_all, causal=True)
        out = decode_attention(q_all[:, -1:], k_all, v_all, jnp.int32(s))
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(full[:, -1]),
                                   rtol=1e-5, atol=1e-5)

    def test_decode_sliding_window(self):
        key = jax.random.key(1)
        b, s, h, d, w = 1, 12, 2, 8, 4
        ks = jax.random.split(key, 3)
        q_all = jax.random.normal(ks[0], (b, s, h, d))
        k_all = jax.random.normal(ks[1], (b, s, h, d))
        v_all = jax.random.normal(ks[2], (b, s, h, d))
        full = self_attention(q_all, k_all, v_all, causal=True, window=w)
        out = decode_attention(q_all[:, -1:], k_all, v_all, jnp.int32(s),
                               window=w)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(full[:, -1]),
                                   rtol=1e-5, atol=1e-5)
