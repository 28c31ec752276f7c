"""The plain reference against the program's own model at a tiny size
on the CPU in float32 (at the published widths the comparison is made
on the chip, by every run)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.reference import dense_decoder

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded")


def _tiny():
    with open(os.path.join(DATA, "tiny-config.json")) as f:
        config = json.load(f)
    cell = {"n_layers": 3, "param_dtype": "float32",
            "compute_dtype": "float32"}
    cfg, arch = harness.llama_config(config, cell, 32)
    params = harness.init_params(cfg, 2**31 + 3, None)
    return cfg, arch, params


def test_logits_and_loss_agree_with_apply_llama():
    from tpu_hpc.models import llama2
    from tpu_hpc.models.losses import cross_entropy

    cfg, arch, params = _tiny()
    kw = harness.reference_kwargs(arch)
    tokens = jax.random.randint(jax.random.key(1), (2, 33), 0, cfg.vocab_size)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        want = llama2.apply_llama(params, inputs, cfg)
    got = dense_decoder.logits(
        params, dense_decoder.hidden_states(params, inputs, **kw),
        norm_eps=arch["norm_eps"],
    )
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        dense_decoder.loss(params, inputs, targets, **kw),
        cross_entropy(want, targets), atol=1e-5,
    )


def test_regret_is_zero_for_the_references_own_choice():
    cfg, arch, params = _tiny()
    kw = harness.reference_kwargs(arch)
    tokens = jax.random.randint(jax.random.key(2), (1, 32), 0, cfg.vocab_size)
    lg = dense_decoder.logits(
        params, dense_decoder.hidden_states(params, tokens, **kw),
        norm_eps=arch["norm_eps"],
    )
    positions = jnp.array([[5, 17, 31]])
    best = jnp.argmax(lg[0, positions[0]], axis=-1)[None]
    regret, std = dense_decoder.regret(params, tokens, positions, best, **kw)
    assert float(jnp.max(regret)) == 0.0 and float(jnp.min(std)) > 0
    worst = jnp.argmin(lg[0, positions[0]], axis=-1)[None]
    regret, std = dense_decoder.regret(params, tokens, positions, worst, **kw)
    assert float(jnp.min(regret / std)) > 2.0


def test_the_seed_enters_as_data():
    cfg, _, a = _tiny()
    b = harness.init_params(cfg, 2**31 + 3, None)
    c = harness.init_params(cfg, 2**31 + 4, None)
    leaf = lambda t: np.asarray(t["output"]["kernel"])  # noqa: E731
    assert (leaf(a) == leaf(b)).all() and (leaf(a) != leaf(c)).any()
