"""The plain float32 references agree with ``repro.models`` at a reduced
size on the CPU, dense and MoE."""
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))
sys.path.insert(0, os.path.dirname(__file__))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import _tiny  # noqa: E402
from chipbench import reference, serving, weights  # noqa: E402

SEED = 2 ** 33 + 99

# Both sides compute in float32 from the same float32 weights; they differ
# only in the order of sums (fused einsums, scans, the MoE dispatch's
# gather/scatter against the reference's dense expert loop).  float32
# keeps 24 bits, so each reordered sum is off by ~1e-7 of its magnitude;
# through two layers and the LM head that stays far below 1e-4 of the
# logits' scale, while one wrong operation (a missing norm, a wrong RoPE
# half, a dropped expert) moves logits by ~1e-1 of it.
TOL = 1e-4


def _f32(config):
    return dict(config, engine=dict(config["engine"], dtype="float32"))


def _both(config, tokens):
    from repro import models
    mcfg = serving.model_config(config)
    abstract = models.abstract_params(mcfg)
    params = weights.device_params(abstract, SEED)
    h, _ = models.forward_train(params, mcfg, tokens)
    prog = models.lm_logits(params["embed"], h, mcfg)
    shapes = weights.flat_shapes(abstract)
    key = weights.seed_key(SEED)
    top = weights.top_leaves(shapes, key)
    x = jnp.take(top["tok_embed"], tokens, axis=0)
    for layer in range(config["num_hidden_layers"]):
        x = reference.block(config, x, weights.layer_leaves(
            shapes, key, jnp.uint32(layer)), False)
    ref = reference._logits(config, top, x, False)
    return np.asarray(prog, np.float32), np.asarray(ref, np.float32)


@pytest.mark.parametrize("config", [_tiny.DENSE, _tiny.MOE],
                         ids=["dense", "moe"])
def test_reference_matches_program_in_float32(config):
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, config["vocab_size"], (2, 24)), jnp.int32)
    prog, ref = _both(_f32(config), tokens)
    err = np.max(np.abs(prog - ref)) / np.max(np.abs(ref))
    assert err < TOL, err


def test_weights_made_whole_equal_weights_made_by_layer():
    from repro import models
    mcfg = serving.model_config(_tiny.MOE)
    abstract = models.abstract_params(mcfg)
    whole = weights.device_params(abstract, SEED)
    shapes = weights.flat_shapes(abstract)
    one = weights.layer_leaves(shapes, weights.seed_key(SEED),
                               jnp.uint32(1))
    for name, arr in one.items():
        np.testing.assert_array_equal(
            np.asarray(whole["layers"][name][1], np.float32),
            np.asarray(arr))


def test_greedy_tokens_of_the_reference_read_no_gap():
    """Tokens the float32 reference itself would pick read a gap of 0."""
    config = _f32(_tiny.DENSE)
    prompt = list(range(5, 21))
    out = []
    for _ in range(6):
        _, ref = _both(config, jnp.asarray([prompt + out], jnp.int32))
        out.append(int(np.argmax(ref[0, -1])))
    from repro import models
    shapes = weights.flat_shapes(models.abstract_params(
        serving.model_config(config)))
    g = reference.served_gaps(config, shapes, SEED, [(prompt, out)],
                              control=True)
    assert np.max(g["program"][0]) == pytest.approx(0.0, abs=1e-5)
    assert np.min(g["control"][0]) >= 0.0
