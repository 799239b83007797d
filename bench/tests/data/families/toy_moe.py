"""A test family: a decoder whose every feed-forward is a mixture of
SwiGLU experts, at toy widths. A test copies this file into a toy
checkout's ``bench/families/``, beside a configuration naming
``"family": "toy_moe"``, to show that a family with another parameter
layout enters the benchmark with new files only.

The reference is a plain float32 forward at ``Precision.HIGHEST``: the
dense family's attention, then a router whose softmax over all experts
is cut to its top ``k`` and renormalised over them, and the weighted
sum of those experts' SwiGLUs. Every expert runs on every token; the
weights of those not chosen are zero.

``arch_config`` maps onto the program's ``family="moe"`` with a
capacity factor of ``num_experts / top_k``: each expert then has room
for every token of a batch, so no token is dropped. That is this
fixture's mapping, not a real family's.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.families import dense
from bench.families.dense import (BUCKET, HI, Q_BLOCK, _fp8, _mm, _rms,
                                  _rope)


def dims(conf: dict) -> dict:
    model = conf["model"]
    if model["hidden_act"] != "silu" or not model["norm_topk_prob"]:
        raise ValueError("toy_moe: SwiGLU experts with renormalised "
                         "top-k weights only")
    return {
        "d": model["hidden_size"], "h": model["num_attention_heads"],
        "hkv": model["num_key_value_heads"], "dh": model["head_dim"],
        "ff": model["moe_intermediate_size"],
        "experts": model["num_experts"], "k": model["num_experts_per_tok"],
        "vocab": model["vocab_size"], "layers": model["num_hidden_layers"],
        "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "qk_norm": bool(model["qk_norm"]),
        "tied": bool(model["tie_word_embeddings"]),
    }


def layout(conf: dict, tp: int = 1, dtype=jnp.bfloat16) -> dict:
    m = dims(conf)
    d, h, hkv, dh, ff, E, L = (m["d"], m["h"], m["hkv"], m["dh"], m["ff"],
                               m["experts"], m["layers"])
    vp = -(-m["vocab"] // tp) * tp
    sd = lambda *s: jax.ShapeDtypeStruct(s, dtype)      # noqa: E731
    attn = {"wq": sd(L, d, h * dh), "wk": sd(L, d, hkv * dh),
            "wv": sd(L, d, hkv * dh), "wo": sd(L, h * dh, d)}
    if m["qk_norm"]:
        attn["q_norm"] = {"scale": sd(L, dh)}
        attn["k_norm"] = {"scale": sd(L, dh)}
    mlp = {"router": sd(L, d, E), "wu": sd(L, E, d, ff),
           "wg": sd(L, E, d, ff), "wd": sd(L, E, ff, d)}
    tree = {"embed": {"table": sd(vp, d)}, "ln_f": {"scale": sd(d)},
            "blocks": {"ln1": {"scale": sd(L, d)}, "attn": attn,
                       "ln2": {"scale": sd(L, d)}, "mlp": mlp}}
    if not m["tied"]:
        tree["head"] = {"table": sd(vp, d)}
    return tree


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies in one layer: q, k, v, o, the
    router and its ``k`` experts."""
    d, h, hkv, dh = m["d"], m["h"], m["hkv"], m["dh"]
    return (d * (h + 2 * hkv) * dh + h * dh * d + d * m["experts"]
            + m["k"] * 3 * d * m["ff"])


def model_flops(m: dict, new_tokens: int, attended: int,
                logit_rows: int) -> float:
    L = m["layers"]
    return float(2 * new_tokens * layer_matmul_params(m) * L
                 + 4 * attended * m["h"] * m["dh"] * L
                 + 2 * logit_rows * m["d"] * m["vocab"])


def arch_config(conf: dict):
    from repro import configs
    from repro.configs.base import MoEConfig
    m = dims(conf)
    return dataclasses.replace(
        configs.get(conf["arch"]), name=conf["name"], family="moe",
        n_layers=m["layers"], d_model=m["d"], n_heads=m["h"], n_kv=m["hkv"],
        head_dim=m["dh"], d_ff=m["ff"], vocab=m["vocab"], act="swiglu",
        qk_norm=m["qk_norm"], rope_theta=m["theta"],
        tie_embeddings=m["tied"], norm_eps=m["eps"],
        moe=MoEConfig(num_experts=m["experts"], top_k=m["k"],
                      expert_ff=m["ff"],
                      capacity_factor=m["experts"] / m["k"]))


@functools.partial(jax.jit, static_argnames=("sizes", "fp8"))
def _layer(w, x, sizes, fp8):
    """One layer over one sequence ``x`` (T, d)."""
    m = dict(sizes)
    f = {k: v.astype(jnp.float32) for k, v in w.items()}
    T = x.shape[0]
    h, hkv, dh, eps = m["h"], m["hkv"], m["dh"], m["eps"]
    pos = jnp.arange(T)
    y = _rms(x, f["blocks/ln1/scale"], eps)
    q = _mm(y, f["blocks/attn/wq"], fp8).reshape(T, h, dh)
    k = _mm(y, f["blocks/attn/wk"], fp8).reshape(T, hkv, dh)
    v = _mm(y, f["blocks/attn/wv"], fp8).reshape(T, hkv, dh)
    if m["qk_norm"]:
        q = _rms(q, f["blocks/attn/q_norm/scale"], eps)
        k = _rms(k, f["blocks/attn/k_norm/scale"], eps)
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    kx = jnp.repeat(k, h // hkv, axis=1)
    vx = jnp.repeat(v, h // hkv, axis=1)
    outs = []
    for s in range(0, T, Q_BLOCK):
        qb = q[s:s + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, kx, precision=HI) / math.sqrt(dh)
        mask = (s + jnp.arange(qb.shape[0]))[:, None] >= jnp.arange(T)[None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, vx, precision=HI))
    o = jnp.concatenate(outs, 0).reshape(T, h * dh)
    x = x + _mm(o, f["blocks/attn/wo"], fp8)
    y = _rms(x, f["blocks/ln2/scale"], eps)
    gates = jax.nn.softmax(_mm(y, f["blocks/mlp/router"], fp8), axis=-1)
    top, idx = jax.lax.top_k(gates, m["k"])
    top = top / top.sum(-1, keepdims=True)
    share = jnp.zeros_like(gates).at[pos[:, None], idx].set(top)   # (T, E)
    out = jnp.zeros_like(x)
    for e in range(m["experts"]):
        a = (jax.nn.silu(_mm(y, f["blocks/mlp/wg"][e], fp8))
             * _mm(y, f["blocks/mlp/wu"][e], fp8))
        out = out + share[:, e:e + 1] * _mm(a, f["blocks/mlp/wd"][e], fp8)
    return x + out


class Reference(dense.Reference):
    """The dense family's head over this family's layers; ``top_k``
    (tests only) routes each token to another number of experts than
    the configuration states."""

    def __init__(self, conf: dict, seed: int, top_k: int | None = None):
        self.conf, self.seed = conf, int(seed)
        self.m = dims(conf)
        if top_k is not None:
            self.m["k"] = top_k
        self.tree = layout(conf)
        self._key = tuple(sorted(self.m.items()))

    def hidden(self, seqs: list, rows: list, precision: str = "f32"):
        fp8 = precision == "fp8"
        emb = self._top("embed/table")
        xs = []
        for ids in seqs:
            pad = np.zeros(-(-len(ids) // BUCKET) * BUCKET, np.int32)
            pad[:len(ids)] = ids
            xs.append(emb[jnp.asarray(pad)].astype(jnp.float32))
        for li in range(self.m["layers"]):
            w = weights.make_layer(self.tree, self.seed, li)
            xs = [_layer(w, x, self._key, fp8) for x in xs]
        lnf = self._top("ln_f/scale").astype(jnp.float32)
        return jnp.concatenate(
            [_rms(x[jnp.asarray(r, jnp.int32)], lnf, self.m["eps"])
             for x, r in zip(xs, rows)], 0)
