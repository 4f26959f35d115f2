"""The MLA reference against the program's model at the repository's
smoke size (`get_smoke_config("minicpm3-4b")`), on the benchmark's own
seeded weights: a prompt through `Model.prefill`, then tokens through
`Model.decode_step` and its latent cache, against one reference forward
over the whole sequence."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import minicpm3 as ref

SEED = 2**31 + 5
PROMPT, DECODE = 40, 6


def _run_program(spec, dtype: str):
    from repro.configs import get_smoke_config
    from repro.models import Model
    cfg = dataclasses.replace(get_smoke_config("minicpm3-4b"), dtype=dtype)
    model = Model(cfg)
    m = spec["config"]["model"]
    params = weights.program_params(jax.random.key(SEED), m,
                                    cfg.padded_vocab,
                                    Model(get_smoke_config(
                                        "minicpm3-4b")).abstract_params())
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg.vocab_size, PROMPT + DECODE)
    logits, cache = model.prefill(params, jnp.asarray(seq[None, :PROMPT]),
                                  None, max_len=PROMPT + DECODE)
    out = [logits[0, -1, :cfg.vocab_size]]
    for t in seq[PROMPT:PROMPT + DECODE - 1]:
        logits, cache = model.decode_step(params, cache,
                                          jnp.asarray([[t]], jnp.int32))
        out.append(logits[0, -1, :cfg.vocab_size])
    got = np.stack([np.asarray(x, np.float32) for x in out])
    want = np.asarray(ref.logits(SEED, m, cfg.padded_vocab, seq[:-1],
                                 np.arange(PROMPT - 1, PROMPT + DECODE - 1)))
    return got, want


def test_float32_program_matches_reference(serve_spec):
    # Same equations in the same precision: only the order of float32
    # sums differs (fused vs blocked attention, einsum vs matmul). On the
    # host CPU the largest difference read 8.6e-7 against logits up to
    # 1.36, so 1e-4 leaves two orders of room and still fails any
    # departure in the equations.
    got, want = _run_program(serve_spec, "float32")
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_bfloat16_program_tracks_reference(serve_spec):
    # The served precision: bf16 weights, activations and cache round
    # each product to 8 bits of mantissa. Two layers of it read a
    # relative L2 error of 0.0072 against the float32 reference, with the
    # greedy choice unchanged at every position. 0.02 sits near the
    # geometric middle between that and the float8 control's 0.056.
    got, want = _run_program(serve_spec, "bfloat16")
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 0.02
    assert (got.argmax(-1) == want.argmax(-1)).mean() >= 0.8


def test_fp8_control_moves_logits(serve_spec):
    # float8 matrices read 0.056 relative L2, eight times the bf16
    # program's error at this size, and fail the bf16 bound above.
    m = serve_spec["config"]["model"]
    seq = np.random.default_rng(1).integers(0, m["vocab_size"], 64)
    read = np.arange(32, 64)
    exact = np.asarray(ref.logits(SEED, m, 1024, seq, read))
    fp8 = np.asarray(ref.logits(SEED, m, 1024, seq, read, fp8=True))
    assert np.linalg.norm(fp8 - exact) / np.linalg.norm(exact) > 0.02
