"""The dense decoder family: its plain reference, a forward pass in
float32 at ``Precision.HIGHEST`` written from the configuration alone;
its parameter layout; its work count; and its mapping onto the
program's ``ArchConfig``.

The reference imports nothing of the program and takes nothing the
program made: it knows the parameter layout from the configuration and
makes each layer's weights again from the seed (``bench/weights.py``),
one layer at a time, so it fits on the chip after the program's state
is freed.
Each sequence is processed whole (no cache, no batching, no kernels);
attention is causal softmax over all earlier positions, computed in
blocks of query rows.

``precision="fp8"`` is the control: every matrix product's operands
rounded to float8 e4m3 (weights per output column, activations per
row, q/k/v per head vector, each with its own scale) with float32
accumulation -- the step below the bfloat16 the configurations state.

Only ``arch_config`` imports the program, inside the function; the
harness alone calls it.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024            # query rows per attention block
V_BLOCK = 32768           # vocabulary rows per head block
BUCKET = 512              # sequence lengths are padded to a multiple
ROW_PAD = 256             # scored rows are padded to a multiple
ACTS = ("silu", "relu2")


def dims(conf: dict) -> dict:
    """The sizes the forward pass needs, from the configuration's keys."""
    model = conf["model"]
    d = {
        "d": model["hidden_size"], "h": model["num_attention_heads"],
        "hkv": model["num_key_value_heads"], "dh": model["head_dim"],
        "ff": model["intermediate_size"], "vocab": model["vocab_size"],
        "layers": model["num_hidden_layers"],
        "theta": float(model["rope_theta"]),
        "eps": float(model.get("rms_norm_eps", model.get("norm_eps"))),
        "act": model["hidden_act"], "qk_norm": bool(model["qk_norm"]),
        "tied": bool(model["tie_word_embeddings"]),
    }
    if d["act"] not in ACTS:
        raise ValueError(f"reference: activation {d['act']!r} not in {ACTS}")
    return d


def layout(conf: dict, tp: int = 1, dtype=jnp.bfloat16) -> dict:
    """The parameter tree the program is expected to hold on each of
    ``tp`` chips' worth of a layer, as global ShapeDtypeStructs."""
    m = dims(conf)
    d, h, hkv, dh, ff, L = (m["d"], m["h"], m["hkv"], m["dh"], m["ff"],
                            m["layers"])
    vp = -(-m["vocab"] // tp) * tp
    sd = lambda *s: jax.ShapeDtypeStruct(s, dtype)      # noqa: E731
    attn = {"wq": sd(L, d, h * dh), "wk": sd(L, d, hkv * dh),
            "wv": sd(L, d, hkv * dh), "wo": sd(L, h * dh, d)}
    if m["qk_norm"]:
        attn["q_norm"] = {"scale": sd(L, dh)}
        attn["k_norm"] = {"scale": sd(L, dh)}
    mlp = {"wu": sd(L, d, ff), "wd": sd(L, ff, d)}
    if m["act"] == "silu":
        mlp["wg"] = sd(L, d, ff)
    tree = {"embed": {"table": sd(vp, d)}, "ln_f": {"scale": sd(d)},
            "blocks": {"ln1": {"scale": sd(L, d)}, "attn": attn,
                       "ln2": {"scale": sd(L, d)}, "mlp": mlp}}
    if not m["tied"]:
        tree["head"] = {"table": sd(vp, d)}
    return tree


# ----------------------------------------------------------------------
def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    """(..., k) @ (k, n) in float32 at HIGHEST, or fp8 operands."""
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * freqs          # (T, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("sizes", "fp8"))
def _layer(w, x, sizes, fp8):
    """One decoder layer over one sequence ``x`` (T, d); ``sizes`` is
    ``dims()`` as a tuple of items (a static argument is hashable)."""
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
    # GQA: query head i reads kv head i // (h / hkv)
    kx = jnp.repeat(k, h // hkv, axis=1)
    vx = jnp.repeat(v, h // hkv, axis=1)
    outs = []
    for s in range(0, T, Q_BLOCK):
        qb = q[s:s + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, kx, precision=HI) / math.sqrt(dh)
        mask = (s + jnp.arange(qb.shape[0]))[:, None] >= jnp.arange(T)[None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, vx, precision=HI))
    o = jnp.concatenate(outs, 0).reshape(T, h * dh)
    x = x + _mm(o, f["blocks/attn/wo"], fp8)
    y = _rms(x, f["blocks/ln2/scale"], eps)
    if m["act"] == "silu":
        g = _mm(y, f["blocks/mlp/wg"], fp8)
        a = jax.nn.silu(g) * _mm(y, f["blocks/mlp/wu"], fp8)
    else:
        a = jnp.square(jax.nn.relu(_mm(y, f["blocks/mlp/wu"], fp8)))
    return x + _mm(a, f["blocks/mlp/wd"], fp8)


@functools.partial(jax.jit, static_argnames=("fp8",))
def _head_block(hs, table, fp8):
    """Logits of the rows ``hs`` against one block of the head table."""
    t = table.astype(jnp.float32)
    if fp8:
        return jnp.matmul(_fp8(hs, -1), _fp8(t, -1).T, precision=HI)
    return jnp.matmul(hs, t.T, precision=HI)


class Reference:
    """The reference model of one configuration under one seed."""

    def __init__(self, conf: dict, seed: int):
        self.conf, self.seed = conf, int(seed)
        self.m = dims(conf)
        self.tree = layout(conf)
        self._key = tuple(sorted(self.m.items()))

    def _top(self, path):
        return weights.make_top(self.tree, self.seed, path)

    def hidden(self, seqs: list, rows: list, precision: str = "f32"):
        """Final-norm hidden states (n_rows, d), float32, of each
        sequence ``seqs[i]`` (token ids) at its positions ``rows[i]``,
        concatenated in order."""
        fp8 = precision == "fp8"
        m = self._key
        emb = self._top("embed/table")
        xs = []
        for ids in seqs:
            T = -(-len(ids) // BUCKET) * BUCKET
            pad = np.zeros(T, np.int32)
            pad[:len(ids)] = ids
            xs.append(emb[jnp.asarray(pad)].astype(jnp.float32))
        del emb
        for li in range(self.m["layers"]):
            w = weights.make_layer(self.tree, self.seed, li)
            xs = [_layer(w, x, m, fp8) for x in xs]
            del w
        lnf = self._top("ln_f/scale").astype(jnp.float32)
        out = [_rms(x[jnp.asarray(r, jnp.int32)], lnf, self.m["eps"])
               for x, r in zip(xs, rows)]
        return jnp.concatenate(out, 0)

    def logits_stats(self, hs, tokens=None, precision: str = "f32"):
        """Over the whole vocabulary, for each row of ``hs``: the largest
        logit, its token (lowest id on ties), and the logit of
        ``tokens[row]`` (when given)."""
        fp8 = precision == "fp8"
        table = self._top("embed/table" if self.m["tied"] else "head/table")
        V = self.m["vocab"]
        n = hs.shape[0]
        rows = -(-n // ROW_PAD) * ROW_PAD     # few shapes, few compiles
        hs = jnp.pad(hs, ((0, rows - n), (0, 0)))
        best = jnp.full((rows,), -jnp.inf)
        arg = jnp.zeros((rows,), jnp.int32)
        picked = jnp.zeros((rows,))
        tok = None if tokens is None else jnp.asarray(
            np.pad(np.asarray(tokens), (0, rows - n)), jnp.int32)
        for s in range(0, V, V_BLOCK):
            lg = _head_block(hs, table[s:min(s + V_BLOCK, V)], fp8)
            bm, ba = lg.max(-1), lg.argmax(-1).astype(jnp.int32) + s
            arg = jnp.where(bm > best, ba, arg)
            best = jnp.maximum(best, bm)
            if tok is not None:
                inb = (tok >= s) & (tok < s + lg.shape[1])
                got = jnp.take_along_axis(
                    lg, jnp.clip(tok - s, 0, lg.shape[1] - 1)[:, None], 1)[:, 0]
                picked = jnp.where(inb, got, picked)
        return (np.asarray(best)[:n], np.asarray(arg)[:n],
                None if tok is None else np.asarray(picked)[:n])


# ----------------------------------------------------------------------
def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies in one decoder layer (whole model,
    all chips): q, k, v, o and the feed-forward."""
    d, h, hkv, dh, ff = m["d"], m["h"], m["hkv"], m["dh"], m["ff"]
    glu = 3 if m["act"] == "silu" else 2
    return d * (h + 2 * hkv) * dh + h * dh * d + glu * d * ff


def model_flops(m: dict, new_tokens: int, attended: int,
                logit_rows: int) -> float:
    """Model FLOPs (whole model, all chips) of processing ``new_tokens``
    tokens that together attend to ``attended`` cached positions in
    each layer, and of ``logit_rows`` rows of the output head."""
    L = m["layers"]
    return float(2 * new_tokens * layer_matmul_params(m) * L
                 + 4 * attended * m["h"] * m["dh"] * L
                 + 2 * logit_rows * m["d"] * m["vocab"])


ACT = {"silu": "swiglu", "relu2": "relu2"}


def arch_config(conf: dict):
    """The program's ArchConfig for the configuration file: the
    registered architecture with every size the file states."""
    from repro import configs
    m = conf["model"]
    base = configs.get(conf["arch"])
    return dataclasses.replace(
        base, name=conf["name"], n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        act=ACT[m["hidden_act"]], qk_norm=bool(m["qk_norm"]),
        rope_theta=float(m["rope_theta"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        norm_eps=float(m.get("rms_norm_eps", m.get("norm_eps"))))
