"""The dense decoder family: Llama and Mistral.

A configuration file (``bench/configs/<config>.json``) names its family
with ``"family"``; without the key it is this one. A family module
gives the harness everything it knows of the model:

* ``shapes(conf)``: the sizes the counting functions and metric readers
  read (``L``, ``d``, ``H``, ``Hk``, ``D``, ``V``, ``windows``: each
  layer's attention window or ``None``, and ``mm_flops_per_token``: the
  projections and feed-forward work of one token over all layers);
* ``model_config(conf, s)``: the ``repro.models.config.ModelConfig``
  the serving engine serves;
* ``init_params(conf, seed, device)``: the weights, on the device in one
  jitted call from the seed, in the type they are served in (bfloat16)
  and in the layout the engine reads: per-layer leaves stacked on a
  leading layer axis, attention weights head-split (``wq [L, d, H,
  hd]``, ``wo [L, H, hd, d]``), the gated MLP as ``w1`` (gate), ``w3``
  (up) and ``w2`` (down), and each RMSNorm gain stored as its offset
  from 1;
* ``logits(conf, params, tokens, *, low=False, pad_to=0)``: the plain
  reference, the decoder's forward pass in float32 ``jax.numpy`` at
  ``highest`` matmul precision, written from the published
  Llama/Mistral description (RMSNorm, rotate-half RoPE, grouped-query
  causal attention, Mistral's sliding window, SwiGLU, tied or untied
  head). It imports nothing of the program and runs one layer's weights
  at a time. With ``low``, the control: the same forward with every
  matmul operand rounded to float8 (e4m3, one scale per tensor), the
  precision below the configuration's bfloat16.

Another family may build on this one with ``from families import
dense``.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def windows(conf: dict) -> list:
    """Each layer's attention window, or ``None`` for full attention:
    ``sliding_window`` in every layer (Mistral), or in the layers that
    ``layer_types`` marks ``sliding_attention``."""
    L, w = conf["num_hidden_layers"], conf.get("sliding_window")
    types = conf.get("layer_types") or ["sliding_attention"] * L
    if len(types) != L:
        raise ValueError(f"layer_types lists {len(types)} layers, "
                         f"num_hidden_layers is {L}")
    return [w if t == "sliding_attention" else None for t in types]


def shapes(conf: dict) -> dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    s = dict(L=conf["num_hidden_layers"], d=d, H=H,
             Hk=conf["num_key_value_heads"],
             D=conf.get("head_dim") or d // H,
             ff=conf["intermediate_size"], V=conf["vocab_size"],
             tied=bool(conf["tie_word_embeddings"]), windows=windows(conf))
    per_layer = (d * H * s["D"] + 2 * d * s["Hk"] * s["D"] + H * s["D"] * d
                 + 3 * d * s["ff"])
    # multiply and add count as two operations; the head is counted apart
    s["mm_flops_per_token"] = 2 * s["L"] * per_layer
    return s


def model_config(conf: dict, s: dict):
    """The served config. ``ModelConfig`` holds one window for every
    layer, so a configuration whose layers differ in their window needs
    a family of its own."""
    from repro.models.config import ModelConfig
    if len(set(s["windows"])) > 1:
        raise ValueError(f"{conf['name']}: windows differ by layer "
                         f"({s['windows']}); ModelConfig has one for all")
    return ModelConfig(
        name=conf["name"], family="dense", n_layers=s["L"], d_model=s["d"],
        n_heads=s["H"], n_kv_heads=s["Hk"], head_dim=s["D"], d_ff=s["ff"],
        vocab=s["V"], rope_theta=float(conf["rope_theta"]),
        sliding_window=s["windows"][0], norm="rmsnorm",
        act="swiglu", pos="rope", tie_embeddings=s["tied"])


def jax_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)


def init_params(conf: dict, seed: int, device=None) -> Dict:
    """The model's weights from ``seed``, made on ``device`` in one
    jitted call. Projections have standard deviation ``fan_in ** -0.5``;
    the embedding and head deviations come from the file's ``init``
    block, chosen so that the logits have a spread of a few units."""
    s, init = shapes(conf), conf["init"]
    L, d, H, Hk, D, ff, V = (s[k] for k in ("L", "d", "H", "Hk", "D", "ff",
                                             "V"))
    bf = jnp.bfloat16

    def make(key):
        ks = iter(jax.random.split(key, 12))

        def normal(shape, std):
            return jax.random.normal(next(ks), shape, bf) * jnp.asarray(
                std, bf)

        g = init["norm_gain_std"]
        p = {
            "embed": {"table": normal((V, d), init["embed_std"])},
            "layers": {
                "ln1": {"scale": normal((L, d), g)},
                "attn": {"wq": normal((L, d, H, D), d ** -0.5),
                         "wk": normal((L, d, Hk, D), d ** -0.5),
                         "wv": normal((L, d, Hk, D), d ** -0.5),
                         "wo": normal((L, H, D, d), (H * D) ** -0.5)},
                "ln2": {"scale": normal((L, d), g)},
                "mlp": {"w1": normal((L, d, ff), d ** -0.5),
                        "w3": normal((L, d, ff), d ** -0.5),
                        "w2": normal((L, ff, d), ff ** -0.5)},
            },
            "final_norm": {"scale": normal((d,), g)},
        }
        if not s["tied"]:
            p["lm_head"] = {"table": normal((V, d), init["head_std"])}
        return p

    out = None if device is None else jax.sharding.SingleDeviceSharding(
        device)
    return jax.jit(make, out_shardings=out)(jax_key(seed))


# --- the plain reference ----------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fake_f8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under one absmax scale, back in f32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(spec: str, a, b, low: bool):
    if low:
        a, b = _fake_f8(a), _fake_f8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms_norm(x, offset, eps):
    gain = 1.0 + offset.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """Rotate-half RoPE on ``[T, heads, D]`` at positions ``0..T-1``."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("conf_key", "low"))
def _forward(params, tokens, *, conf_key, low):
    """Logits ``[T, V]`` (float32) of one token sequence ``[T]``."""
    conf = dict(conf_key)
    s = shapes(conf)
    H, Hk, D = s["H"], s["Hk"], s["D"]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    T = tokens.shape[0]
    f32 = jnp.float32
    x = params["embed"]["table"][tokens].astype(f32)              # [T, d]
    causal = jnp.tril(jnp.ones((T, T), bool))
    # key j is visible to row i when i - w < j <= i (Mistral); a window
    # that spans the whole sequence leaves the causal mask as it is
    bands = [w for w in s["windows"] if w is not None and w < T]
    if bands:
        wins = jnp.asarray([T if w is None else min(w, T)
                            for w in s["windows"]], jnp.int32)
        behind = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]   # i - j
        xs = (params["layers"], wins)
    else:
        xs = (params["layers"], None)

    def layer(x, xs):
        lp, w = xs
        visible = causal if w is None else causal & (behind < w)
        lp = jax.tree_util.tree_map(lambda a: a.astype(f32), lp)
        h = _rms_norm(x, lp["ln1"]["scale"], eps)
        q = _rope(_mm("td,dhk->thk", h, lp["attn"]["wq"], low), theta)
        k = _rope(_mm("td,dhk->thk", h, lp["attn"]["wk"], low), theta)
        v = _mm("td,dhk->thk", h, lp["attn"]["wv"], low)
        k = jnp.repeat(k, H // Hk, axis=1)        # q head h reads kv h // g
        v = jnp.repeat(v, H // Hk, axis=1)
        sc = _mm("thk,shk->hts", q, k, low) * D ** -0.5
        p = jax.nn.softmax(jnp.where(visible, sc, -jnp.inf), axis=-1)
        a = _mm("hts,shk->thk", p, v, low)
        x = x + _mm("thk,hkd->td", a, lp["attn"]["wo"], low)
        h = _rms_norm(x, lp["ln2"]["scale"], eps)
        gate = _mm("td,df->tf", h, lp["mlp"]["w1"], low)
        up = _mm("td,df->tf", h, lp["mlp"]["w3"], low)
        x = x + _mm("tf,fd->td", jax.nn.silu(gate) * up, lp["mlp"]["w2"], low)
        return x, None

    x, _ = jax.lax.scan(layer, x, xs)
    x = _rms_norm(x, params["final_norm"]["scale"], eps)
    head = params["embed" if s["tied"] else "lm_head"]["table"].astype(f32)
    return _mm("td,vd->tv", x, head, low)


def _conf_key(conf: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "num_hidden_layers", "intermediate_size", "vocab_size",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings", "head_dim",
            "sliding_window", "layer_types")
    return tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in ((k, conf.get(k)) for k in keys))


def logits(conf: dict, params, tokens: np.ndarray, *, low: bool = False,
           pad_to: int = 0) -> np.ndarray:
    """Reference (or, with ``low``, control) logits of ``tokens``,
    computed over a length padded to ``pad_to`` so that one compiled
    program serves every request of a cell; padding sits after the
    sequence, where causal masking keeps it from every row kept."""
    T = len(tokens)
    full = np.zeros(max(T, pad_to), np.int32)
    full[:T] = tokens
    out = _forward(params, jnp.asarray(full), conf_key=_conf_key(conf),
                   low=low)
    return np.asarray(out[:T])
