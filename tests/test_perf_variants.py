"""§Perf optimization variants must be EXACT (up to fp tolerance) against
the baseline formulations — correctness gates for the hillclimb."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import Model
from repro.models.attention import chunked_self_attention, self_attention


class TestChunkedAttention:
    @pytest.mark.parametrize("window", [0, 24])
    @pytest.mark.parametrize("chunk", [16, 64])
    def test_matches_naive(self, window, chunk):
        ks = jax.random.split(jax.random.key(0), 3)
        b, s, h, hkv, d = 2, 96, 4, 2, 32
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, s, hkv, d))
        v = jax.random.normal(ks[2], (b, s, hkv, d))
        naive = self_attention(q, k, v, causal=True, window=window)
        chunked = chunked_self_attention(q, k, v, causal=True,
                                         window=window, chunk=chunk)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(naive),
                                   rtol=2e-5, atol=2e-5)

    def test_differentiable(self):
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (1, 32, 2, 16))
        k = jax.random.normal(ks[1], (1, 32, 2, 16))
        v = jax.random.normal(ks[2], (1, 32, 2, 16))

        def loss_naive(q):
            return jnp.sum(self_attention(q, k, v) ** 2)

        def loss_chunked(q):
            return jnp.sum(chunked_self_attention(q, k, v, chunk=8) ** 2)

        g1 = jax.grad(loss_naive)(q)
        g2 = jax.grad(loss_chunked)(q)
        np.testing.assert_allclose(np.asarray(g2), np.asarray(g1),
                                   rtol=1e-4, atol=1e-4)

    def test_model_loss_identical(self):
        cfg = get_smoke_config("llama3-8b")
        cfg_c = dataclasses.replace(cfg, attn_chunk=16)
        key = jax.random.key(2)
        params = Model(cfg).init(key)
        batch = {"tokens": jax.random.randint(key, (2, 48), 0,
                                              cfg.vocab_size)}
        l1 = jax.jit(Model(cfg).loss)(params, batch)
        l2 = jax.jit(Model(cfg_c).loss)(params, batch)
        assert float(l1) == pytest.approx(float(l2), rel=2e-3)


class TestMLAAbsorbed:
    """MLA decode attends against the latent cache in the absorbed form;
    prefill expands per-head K and V from the same latents (the naive
    form). Decoding must read what a prefill of the grown sequence reads
    at each position."""

    @staticmethod
    def _prefill(model, params, max_len):
        """seq -> (the last position's logits, its one-row cache)."""
        fn = jax.jit(lambda p, t: model.prefill(p, t, None,
                                                max_len=max_len))

        def run(seq):
            logits, cache = fn(params, jnp.asarray(seq, jnp.int32)[None])
            return logits[0, -1, :model.cfg.vocab_size], cache
        return run

    @staticmethod
    def _splice(rows):
        """One-row prefill caches spliced into one cache, as the serving
        engine does: `pos` is a per-row vector."""
        cache = {k: jnp.concatenate([c[k] for c in rows], axis=1)
                 for k in rows[0] if k != "pos"}
        cache["pos"] = jnp.stack([c["pos"] for c in rows])
        return cache

    def test_decode_matches_naive(self):
        """float32: decode steps over rows of different lengths give the
        logits, and write the latents, that a prefill of each row's
        sequence gives at the same position, to 1e-4."""
        cfg = dataclasses.replace(get_smoke_config("minicpm3-4b"),
                                  dtype="float32")
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        steps, max_len = 3, 16
        rng = np.random.default_rng(0)
        seqs = [rng.integers(0, cfg.vocab_size, n + steps) for n in (5, 8)]
        lens = [len(s) - steps for s in seqs]
        prefill = self._prefill(model, params, max_len)
        cache = self._splice([prefill(s[:n])[1] for s, n in zip(seqs, lens)])
        step = jax.jit(model.decode_step)
        for k in range(steps):
            tok = jnp.asarray([[s[n + k]] for s, n in zip(seqs, lens)],
                              jnp.int32)
            logits, cache = step(params, cache, tok)
            for row, (s, n) in enumerate(zip(seqs, lens)):
                want, ref_cache = prefill(s[:n + k + 1])
                np.testing.assert_allclose(
                    np.asarray(logits[row, 0, :cfg.vocab_size]),
                    np.asarray(want), rtol=1e-4, atol=1e-4,
                    err_msg=f"row {row} step {k}")
                np.testing.assert_allclose(
                    np.asarray(cache["latent"][:, row, :n + k + 1]),
                    np.asarray(ref_cache["latent"][:, 0, :n + k + 1]),
                    rtol=1e-4, atol=1e-4, err_msg=f"row {row} step {k}")
        np.testing.assert_array_equal(
            np.asarray(cache["pos"]), [n + steps for n in lens])

    def test_multi_step_consistency(self):
        """bfloat16, as served: greedy decode picks, at every step and
        row, the token that a prefill of the grown sequence ranks
        first."""
        cfg = get_smoke_config("minicpm3-4b")
        model = Model(cfg)
        params = model.init(jax.random.key(1))
        steps, max_len = 4, 16
        rng = np.random.default_rng(1)
        seqs = [list(rng.integers(0, cfg.vocab_size, n)) for n in (6, 9)]
        prefill = self._prefill(model, params, max_len)
        first = [prefill(s) for s in seqs]
        cache = self._splice([c for _, c in first])
        tok = jnp.asarray([[int(jnp.argmax(lg))] for lg, _ in first],
                          jnp.int32)
        step = jax.jit(model.decode_step)
        for k in range(steps):
            for s, t in zip(seqs, np.asarray(tok)[:, 0]):
                s.append(int(t))
            logits, cache = step(params, cache, tok)
            tok = jnp.argmax(logits[..., :cfg.vocab_size], -1).astype(
                jnp.int32)
            for row, s in enumerate(seqs):
                want = prefill(s)[0]
                assert int(tok[row, 0]) == int(jnp.argmax(want)), \
                    f"row {row} step {k}"
                # bf16 logits near 1 are spaced 0.0078; the two forms
                # round K, V and the query side at different points
                np.testing.assert_allclose(
                    np.asarray(logits[row, 0, :cfg.vocab_size], np.float32),
                    np.asarray(want, np.float32), rtol=0.05, atol=0.05,
                    err_msg=f"row {row} step {k}")


class TestSeqParallelNoMesh:
    def test_identity_on_cpu(self):
        """Without a mesh the constraint is a no-op: loss unchanged."""
        cfg = get_smoke_config("llama3-8b")
        cfg_sp = dataclasses.replace(cfg, seq_parallel=True)
        key = jax.random.key(0)
        params = Model(cfg).init(key)
        batch = {"tokens": jax.random.randint(key, (2, 32), 0,
                                              cfg.vocab_size)}
        l1 = jax.jit(Model(cfg).loss)(params, batch)
        l2 = jax.jit(Model(cfg_sp).loss)(params, batch)
        assert float(l1) == float(l2)


class TestLengthShardedDecode:
    def test_matches_naive_under_mesh(self):
        """Exercise the REAL length-sharded math (not the no-mesh
        fallback) under a trivial 1x1 mesh."""
        import jax
        from repro.models.attention import (decode_attention,
                                            decode_attention_length_sharded)
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ks = jax.random.split(jax.random.key(0), 3)
        b, s, h, hkv, d = 2, 64, 8, 2, 16
        q = jax.random.normal(ks[0], (b, 1, h, d))
        kc = jax.random.normal(ks[1], (b, s, hkv, d))
        vc = jax.random.normal(ks[2], (b, s, hkv, d))
        pos = jnp.asarray([40, 64], jnp.int32)
        with jax.set_mesh(mesh):
            out = jax.jit(lambda *a: decode_attention_length_sharded(*a))(
                q, kc, vc, pos)
        ref = decode_attention(q, kc, vc, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [0, 16])
    def test_window_and_scalar_pos(self, window):
        import jax
        from repro.models.attention import (decode_attention,
                                            decode_attention_length_sharded)
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ks = jax.random.split(jax.random.key(1), 3)
        b, s, h, d = 1, 48, 4, 8
        q = jax.random.normal(ks[0], (b, 1, h, d))
        kc = jax.random.normal(ks[1], (b, s, h, d))
        vc = jax.random.normal(ks[2], (b, s, h, d))
        pos = jnp.int32(37)
        with jax.set_mesh(mesh):
            out = jax.jit(lambda *a: decode_attention_length_sharded(
                *a, window=window))(q, kc, vc, pos)
        ref = decode_attention(q, kc, vc, pos, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestSWARingBuffer:
    def test_ring_matches_full_window_decode(self):
        """Ring-buffer decode must produce the same logits as the naive
        full-length cache with window masking, across many steps
        (including wrap-around)."""
        cfg = dataclasses.replace(get_smoke_config("mixtral-8x22b"),
                                  sliding_window=16)
        cfg_r = dataclasses.replace(cfg, swa_ring=True)
        model, model_r = Model(cfg), Model(cfg_r)
        key = jax.random.key(0)
        params = model.init(key)
        tokens = jax.random.randint(key, (2, 12), 0, cfg.vocab_size)
        _, cache = jax.jit(
            lambda p, t: model.prefill(p, t, None, max_len=48))(params,
                                                                tokens)
        _, cache_r = jax.jit(
            lambda p, t: model_r.prefill(p, t, None, max_len=48))(params,
                                                                  tokens)
        assert cache_r["k"].shape[2] == 16  # ring sized to the window
        step = jax.jit(model.decode_step)
        step_r = jax.jit(model_r.decode_step)
        tok = tok_r = jnp.asarray([[3], [9]], jnp.int32)
        for i in range(24):  # runs past the wrap-around at pos 16
            l1, cache = step(params, cache, tok)
            l2, cache_r = step_r(params, cache_r, tok_r)
            np.testing.assert_allclose(
                np.asarray(l2[..., :cfg.vocab_size], np.float32),
                np.asarray(l1[..., :cfg.vocab_size], np.float32),
                rtol=0.05, atol=0.05, err_msg=f"step {i}")
            tok = jnp.argmax(l1[..., :cfg.vocab_size], -1).astype(jnp.int32)
            tok_r = jnp.argmax(l2[..., :cfg.vocab_size], -1).astype(
                jnp.int32)
            np.testing.assert_array_equal(np.asarray(tok),
                                          np.asarray(tok_r))

    def test_short_prefill_pad_path(self):
        cfg = dataclasses.replace(get_smoke_config("mixtral-8x22b"),
                                  sliding_window=64, swa_ring=True)
        model = Model(cfg)
        params = model.init(jax.random.key(1))
        tokens = jax.random.randint(jax.random.key(2), (1, 8), 0,
                                    cfg.vocab_size)
        logits, cache = jax.jit(
            lambda p, t: model.prefill(p, t, None, max_len=128))(params,
                                                                 tokens)
        assert cache["k"].shape[2] == 64
        assert bool(jnp.isfinite(logits).all())
