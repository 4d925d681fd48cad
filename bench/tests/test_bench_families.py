"""The dense family: its weights and reference pinned bit for bit at each
configuration's small widths, and its reference's sliding window
against a row-by-row loop."""

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from families import dense  # noqa: E402


def smoke(config: str, **changes) -> dict:
    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    conf.update(conf["smoke"])
    conf.update(changes)
    return conf


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 of the weights (leaves in key order, as float32), of the
# reference logits and of the control logits of one fixed sequence of 40
# tokens padded to 64, seed 3000000017: recorded on the module these
# functions were moved from
PINNED = {
    "smollm-135m": (
        "8c2cd69747566e4a915630d5d2aec3d0576d3daf76808826c7e030d07422a31e",
        "274a5708cae45f70bcbae316294ce0f26df81c38c2dd84b09bf2cd5a03eb6566",
        "9d4f5efd1f08d7aeb5430f1ec7b21046406f93bdf101538666a4158ac20aebb6"),
    "h2o-danube-1.8b": (
        "4241ab1c32aa018696f76ae74914a140bfbea619c4d6781958c50abaf5719fe1",
        "1c10b2ae8c631adfdca08974530689d3408115eff01dfc2c0dcaa32d31507e82",
        "10880f6b24e32fcbac1a519f4cc304827348f6b2eb7585829f5fe97e299dcc26"),
}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_dense_family_is_pinned_bit_for_bit(config):
    conf = smoke(config)
    params = dense.init_params(conf, 3_000_000_017)
    leaves = jax.tree_util.tree_leaves(params)
    V = dense.shapes(conf)["V"]
    tokens = (np.arange(40, dtype=np.int64) * 7919 % (V - 1) + 1).astype(
        np.int32)
    got = (digest([x.astype(jnp.float32) for x in leaves]),
           digest([dense.logits(conf, params, tokens, pad_to=64)]),
           digest([dense.logits(conf, params, tokens, low=True, pad_to=64)]))
    assert got == PINNED[config]


def test_windows_by_layer():
    conf = smoke("h2o-danube-1.8b")
    assert dense.windows(conf) == [4096, 4096]
    assert dense.windows(smoke("smollm-135m")) == [None] * 4
    types = ["full_attention", "sliding_attention"]
    assert dense.windows(dict(conf, layer_types=types)) == [None, 4096]
    with pytest.raises(ValueError):
        dense.windows(dict(conf, layer_types=types * 2))


def test_model_config_has_one_window():
    conf = smoke("h2o-danube-1.8b")
    assert dense.model_config(conf, dense.shapes(conf)).sliding_window == 4096
    types = ["full_attention", "sliding_attention"]
    mixed = dict(conf, layer_types=types)
    with pytest.raises(ValueError, match="windows differ by layer"):
        dense.model_config(mixed, dense.shapes(mixed))
    whole = dict(conf, layer_types=types[:1] * 2)
    assert dense.model_config(whole, dense.shapes(whole)).sliding_window is None


def test_reference_window_against_a_row_by_row_loop():
    """One layer under a window of 8: row i sees exactly the tokens i - 7
    .. i, so its logits are those of the unwindowed reference's last row
    on just those tokens (RoPE scores depend on distance alone, so the
    shift of positions changes them by rounding only)."""
    w, T = 8, 24
    conf = smoke("h2o-danube-1.8b", num_hidden_layers=1, sliding_window=w)
    full = dict(conf, sliding_window=None)
    params = dense.init_params(conf, 5)
    tokens = np.random.default_rng(0).integers(1, 512, T).astype(np.int32)
    got = dense.logits(conf, params, tokens, pad_to=32)
    causal = dense.logits(full, params, tokens, pad_to=32)
    for i in range(T):
        want = dense.logits(full, params,
                            tokens[max(0, i - w + 1):i + 1])[-1]
        np.testing.assert_allclose(got[i], want, atol=2e-4, rtol=0)
        if i < w:
            np.testing.assert_allclose(got[i], causal[i], atol=2e-4, rtol=0)
        else:
            assert np.abs(got[i] - causal[i]).max() > 1e-2
