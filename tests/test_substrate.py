"""Substrate tests: optimizer, data pipeline, checkpointing, engine.

Property tests guard `hypothesis` with pytest.importorskip so minimal
environments still run the unit tests.
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.training import optimizer as opt


class TestAdamW:
    def _params(self, key=0):
        k = jax.random.key(key)
        return {"w": jax.random.normal(k, (8, 8)),
                "b": jnp.zeros((8,)),
                "nested": {"m": jax.random.normal(k, (4, 8))}}

    def test_descends_quadratic(self):
        cfg = opt.AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=0,
                              total_steps=1000)
        params = self._params()
        state = opt.init_state(params)
        target = jax.tree.map(jnp.zeros_like, params)

        def loss_fn(p):
            return sum(jnp.sum((a - b) ** 2) for a, b in
                       zip(jax.tree.leaves(p), jax.tree.leaves(target)))

        l0 = float(loss_fn(params))
        for _ in range(50):
            grads = jax.grad(loss_fn)(params)
            params, state, _ = opt.apply_updates(params, grads, state, cfg)
        assert float(loss_fn(params)) < 0.1 * l0

    def test_grad_clip(self):
        g = {"w": jnp.full((4,), 100.0)}
        clipped, norm = opt.clip_by_global_norm(g, 1.0)
        assert float(norm) == pytest.approx(200.0)
        total = jnp.sqrt(sum(jnp.sum(x ** 2)
                             for x in jax.tree.leaves(clipped)))
        assert float(total) == pytest.approx(1.0, rel=1e-5)

    def test_weight_decay_only_on_matrices(self):
        cfg = opt.AdamWConfig(lr=0.1, weight_decay=1.0, warmup_steps=0)
        params = {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}
        state = opt.init_state(params)
        grads = jax.tree.map(jnp.zeros_like, params)
        new, _, _ = opt.apply_updates(params, grads, state, cfg)
        assert float(new["w"][0, 0]) < 1.0   # decayed
        assert float(new["b"][0]) == 1.0     # not decayed

    def test_lr_schedule_shape(self):
        cfg = opt.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                              min_lr_frac=0.1)
        lrs = [float(opt.lr_schedule(cfg, jnp.int32(s)))
               for s in (0, 5, 10, 55, 100, 200)]
        assert lrs[0] == 0.0
        assert lrs[1] == pytest.approx(0.5)
        assert lrs[2] == pytest.approx(1.0)
        assert 0.1 < lrs[3] < 1.0
        assert lrs[4] == pytest.approx(0.1, rel=1e-5)
        assert lrs[5] == pytest.approx(0.1, rel=1e-5)

    def test_update_is_finite(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @given(lr=st.floats(1e-5, 1e-2), seed=st.integers(0, 100))
        @settings(max_examples=20, deadline=None)
        def run(lr, seed):
            cfg = opt.AdamWConfig(lr=lr, warmup_steps=0)
            params = self._params(seed)
            state = opt.init_state(params)
            grads = jax.tree.map(
                lambda p: jax.random.normal(jax.random.key(seed), p.shape),
                params)
            new, _state, _m = opt.apply_updates(params, grads, state, cfg)
            for leaf in jax.tree.leaves(new):
                assert bool(jnp.isfinite(leaf).all())

        run()


class TestDataPipeline:
    def test_deterministic(self):
        cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=4, seed=3)
        a = next(SyntheticTokens(cfg))
        b = next(SyntheticTokens(cfg))
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_stream_advances(self):
        cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=4)
        it = SyntheticTokens(cfg)
        a, b = next(it), next(it)
        assert not np.array_equal(a["tokens"], b["tokens"])

    def test_tokens_in_range(self):
        cfg = DataConfig(vocab_size=50, seq_len=32, global_batch=8)
        batch = next(SyntheticTokens(cfg))
        assert batch["tokens"].min() >= 0
        assert batch["tokens"].max() < 50

    def test_markov_structure_learnable(self):
        """Each token has at most `branching` successors."""
        cfg = DataConfig(vocab_size=64, seq_len=256, global_batch=8,
                         branching=4)
        toks = next(SyntheticTokens(cfg))["tokens"]
        succ = {}
        for row in toks:
            for a, b in zip(row[:-1], row[1:]):
                succ.setdefault(int(a), set()).add(int(b))
        assert max(len(s) for s in succ.values()) <= 4


class TestCheckpoint:
    def test_roundtrip(self):
        params = {"w": jnp.arange(6.0).reshape(2, 3),
                  "n": {"b": jnp.ones((4,), jnp.bfloat16)}}
        with tempfile.TemporaryDirectory() as d:
            store.save(d, 10, params)
            out = store.restore(d, params)
            np.testing.assert_array_equal(np.asarray(out["w"]),
                                          np.asarray(params["w"]))
            assert out["n"]["b"].dtype == jnp.bfloat16
            assert store.latest_step(d) == 10
            assert store.meta(d)["step"] == 10

    def test_retention(self):
        params = {"w": jnp.zeros(2)}
        with tempfile.TemporaryDirectory() as d:
            for s in (1, 2, 3, 4, 5):
                store.save(d, s, params, keep=2)
            steps = sorted(os.listdir(d))
            assert len(steps) == 2
            assert store.latest_step(d) == 5

    def test_opt_state_roundtrip(self):
        params = {"w": jnp.ones((3, 3))}
        state = opt.init_state(params)
        with tempfile.TemporaryDirectory() as d:
            store.save(d, 1, params, state)
            out = store.restore(d, state, name="opt_state.npz")
            assert int(out.step) == 0


class TestEngine:
    @pytest.fixture(scope="class")
    def setup(self):
        from repro.configs import get_smoke_config
        from repro.models import Model
        cfg = get_smoke_config("llama3-8b")
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        return cfg, model, params

    def test_serves_batched_requests(self, setup):
        from repro.serving.engine import InferenceEngine
        cfg, model, params = setup
        fake = [0.0]
        eng = InferenceEngine(model, params, max_batch=4, max_len=48,
                              policy="proposed", num_host_cores=8,
                              clock=lambda: fake[0])
        rng = np.random.default_rng(0)
        ids = [eng.submit(rng.integers(0, 999, 8).tolist(), 5)
               for _ in range(6)]
        for _ in range(100):
            if not eng.pending and not eng.active_mask.any():
                break
            eng.step()
            fake[0] += 0.1
        reqs = {r.req_id: r for r in
                [x for x in eng.slots if x] + eng.pending}
        assert not reqs  # drained
        assert eng.host_cpu_report()["assigns"] >= 6 * 3

    def test_engine_matches_sequential_decode(self, setup):
        """Continuous batching must produce the same tokens as dedicated
        single-request decoding (greedy)."""
        from repro.serving.engine import InferenceEngine
        cfg, model, params = setup
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 999, 8).tolist() for _ in range(3)]

        # sequential reference
        want = []
        for p in prompts:
            toks = jnp.asarray(p, jnp.int32)[None, :]
            logits, cache = jax.jit(
                lambda pr, t: model.prefill(pr, t, None, max_len=32)
            )(params, toks)
            out = [int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))]
            for _ in range(3):
                tok = jnp.asarray([[out[-1]]], jnp.int32)
                logits, cache = jax.jit(model.decode_step)(params, cache,
                                                           tok)
                out.append(int(jnp.argmax(logits[0, 0, :cfg.vocab_size])))
            want.append(out)

        eng = InferenceEngine(model, params, max_batch=4, max_len=32,
                              policy="linux", num_host_cores=4)
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        eng.run_until_drained()
        # engine retains outputs on the request objects it created; gather
        # them via the slots history -> track through returned ids instead
        # (requests complete in submission order here)
        # We reconstruct by re-submitting and recording step outputs:
        eng2 = InferenceEngine(model, params, max_batch=4, max_len=32,
                               policy="linux", num_host_cores=4)
        reqs = [eng2.submit(p, max_new_tokens=4) for p in prompts]
        outputs = {r: [] for r in reqs}
        for _ in range(50):
            if not eng2.pending and not eng2.active_mask.any():
                break
            for rid, tok in eng2.step():
                outputs[rid].append(tok)
        for rid, p, w in zip(reqs, prompts, want):
            # first token comes from prefill (recorded at admit), so the
            # stepped tokens are w[1:]
            assert outputs[rid] == w[1:], (rid, outputs[rid], w)


class TestServeFit:
    """`launch.serve` refuses, with the numbers, a model whose weights
    and KV cache exceed the device, instead of dying out of memory."""

    class _Chip:
        device_kind = "TPU v5 lite"

        def memory_stats(self):
            return {"bytes_limit": 16 * 10**9}

    @pytest.mark.parametrize("arch,fits", [("minicpm3-4b", True),
                                           ("llama3-8b", False)])
    def test_full_configs_on_one_v5e(self, monkeypatch, arch, fits):
        from repro.configs import get_config
        from repro.launch import serve
        from repro.models import Model
        monkeypatch.setattr(jax, "devices", lambda: [self._Chip()])
        model = Model(get_config(arch))
        if fits:
            serve.check_fits(model, max_batch=8, max_len=2048)
        else:
            with pytest.raises(SystemExit, match="needs .* GiB"):
                serve.check_fits(model, max_batch=8, max_len=2048)
