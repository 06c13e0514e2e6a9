"""Sizes, seeded weights and the plain reference of a dense decoder with
the serving engine's rank path, in plain ``jax.numpy``.

Nothing here imports the program. The weights are made here from the
run's seed, in the program's parameter layout, and handed to the program;
the reference reads only them, the prompts, the served tokens and the
rank the program reports for each segment.

What the reference computes for one request (prompt of P tokens, n
served tokens o_0..o_{n-1}): the logits that predict each o_i, by one
pass over prompt + o_0..o_{n-2}, layer by layer and in blocks of query
rows, at float32 and ``highest`` matmul precision.

- Prompt queries attend full-rank, causally.
- The query that feeds o_{i-1} (i >= 1) is decode step i - 1 of segment
  s = (i - 1) // segment_len. It scores every key with q^T B_r B_r^T k,
  where B_r holds the top r eigenvectors (per layer and KV head) of the
  attention-mass weighted Gram sum_j w_j k_j k_j^T over the keys that
  existed when the segment began, w_j being the softmax mass key j had
  received from every earlier query (mean over the query heads of its KV
  group), and r the rank the program reports for that segment. Values
  are read at full width.

``lo`` runs the same pass with every matmul input (weights, activations,
q, k, attention probabilities, v, the Gram's keys and the basis) rounded
to a lower precision, products summed in float32, everything else in
float32: bfloat16 (what the TPU's default precision does to a float32
matmul), or float8 e4m3 with an absmax scale per row (per column of a
weight). The control is the configuration's ``check.control``.
"""
from __future__ import annotations

import functools
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
          "float8_e4m3fn": jnp.float8_e4m3fn}


@dataclass(frozen=True)
class Spec:
    """One configuration as run: model sizes, rank path, engine, check."""
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    dtype: str
    rank_mode: str
    rank_grid: Tuple[int, ...]
    segment_len: int
    n_slots: int
    max_len: int
    max_new_cap: int
    gap_limit: float
    control: str
    repro_config: str

    @property
    def r_keep(self) -> int:
        return min(self.rank_grid[-1], self.head_dim)

    def n_params(self) -> int:
        d, dh = self.d_model, self.head_dim
        per = (d * dh * (self.heads + 2 * self.kv_heads)
               + self.heads * dh * d + 3 * d * self.d_ff + 2 * d)
        return self.layers * per + 2 * self.vocab * d + d


def load_spec(path: Path) -> Spec:
    c = json.loads(Path(path).read_text())
    m, r, e, chk = c["model"], c["rank"], c["engine"], c["check"]
    if m.get("tie_word_embeddings"):
        raise ValueError(f"{path}: tied embeddings are not supported")
    return Spec(
        name=c["name"], layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], heads=m["num_attention_heads"],
        kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        rope_theta=float(m["rope_theta"]), rms_eps=float(m["rms_norm_eps"]),
        dtype=m["torch_dtype"], rank_mode=r["mode"],
        rank_grid=tuple(r["rank_grid"]), segment_len=int(r["segment_len"]),
        n_slots=e["n_slots"], max_len=e["max_len"],
        max_new_cap=e["max_new_cap"],
        gap_limit=float(chk["logit_gap_limit"]), control=chk["control"],
        repro_config=c["repro_config"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def seed_key(seed: int):
    """A PRNG key from a seed of any size (the low and high 32 bits)."""
    k = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


@functools.partial(jax.jit, static_argnums=0)
def _init(spec: Spec, key):
    dt = DTYPES[spec.dtype]
    L, d, dh, f = spec.layers, spec.d_model, spec.head_dim, spec.d_ff
    hq, hkv, V = spec.heads, spec.kv_heads, spec.vocab
    ks = iter(jax.random.split(key, 10))

    def w(shape, std):
        return (jax.random.normal(next(ks), shape, F32) * std).astype(dt)

    out_std = (2 * L) ** -0.5
    return {
        "embed": w((V, d), 1.0),
        "layers": {
            "attn": {"wq": w((L, d, hq * dh), d ** -0.5),
                     "wk": w((L, d, hkv * dh), d ** -0.5),
                     "wv": w((L, d, hkv * dh), d ** -0.5),
                     "wo": w((L, hq * dh, d), (hq * dh) ** -0.5 * out_std)},
            "ffn": {"w_gate": w((L, d, f), d ** -0.5),
                    "w_up": w((L, d, f), d ** -0.5),
                    "w_down": w((L, f, d), f ** -0.5 * out_std)},
            "ln1": jnp.ones((L, d), dt),
            "ln2": jnp.ones((L, d), dt),
        },
        "ln_f": jnp.ones((d,), dt),
        "lm_head": w((d, V), d ** -0.5),
    }


def init_params(spec: Spec, seed: int):
    """The model's weights from ``seed``: one jitted call, on the default
    device, in the dtype the configuration serves."""
    return _init(spec, seed_key(seed))


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST


def _round(a, lo: Optional[str], axis: int = -1):
    """``a`` as a matmul input in the control precision (float32 result)."""
    if lo is None:
        return a
    if lo == "bfloat16":
        return a.astype(jnp.bfloat16).astype(F32)
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(DTYPES[lo]).astype(F32) * s


def _mm(a, w, lo):
    return jnp.dot(_round(a, lo), _round(w.astype(F32), lo, axis=0),
                   precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attend(qg, k, v, q_idx, k_idx, valid_k, scale, lo):
    """qg (q, g, r, e), k (k, g, e), v (k, g, dh): causal softmax rows
    over the valid keys; returns outputs (q, g, r, dh) and probs
    (g, r, q, k)."""
    s = jnp.einsum("qgre,kge->grqk", _round(qg, lo), _round(k, lo),
                   precision=HIGHEST) * scale
    ok = (k_idx[None, :] <= q_idx[:, None]) & valid_k[None, :]
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("grqk,kgd->qgrd", _round(p, lo), _round(v, lo, axis=0),
                   precision=HIGHEST)
    return o, p


@functools.partial(jax.jit, static_argnames=(
    "spec", "t0", "n_seg", "qblk", "lo"))
def _layer(lp, x, pos, valid, ranks, *, spec: Spec, t0: int, n_seg: int,
           qblk: int, lo: Optional[str]):
    T = x.shape[0]
    hq, hkv, dh = spec.heads, spec.kv_heads, spec.head_dim
    rep, seg, rk = hq // hkv, spec.segment_len, spec.r_keep
    scale = dh ** -0.5
    a = lp["attn"]
    h = _rms(x, lp["ln1"], spec.rms_eps)
    q = _rope(_mm(h, a["wq"], lo).reshape(T, hq, dh), pos, spec.rope_theta)
    k = _rope(_mm(h, a["wk"], lo).reshape(T, hkv, dh), pos, spec.rope_theta)
    v = _mm(h, a["wv"], lo).reshape(T, hkv, dh)
    qg = q.reshape(T, hkv, rep, dh)
    idx = jnp.arange(T)
    mass = jnp.zeros((hkv, T), F32)
    outs = []
    for b in range(t0 // qblk):                 # prompt rows: full rank
        lo_r, hi_r = b * qblk, (b + 1) * qblk
        o, p = _attend(qg[lo_r:hi_r], k[:hi_r], v[:hi_r], idx[lo_r:hi_r],
                       idx[:hi_r], valid[:hi_r], scale, lo)
        p = p * valid[lo_r:hi_r][None, None, :, None]
        mass = mass.at[:, :hi_r].add(p.sum(2).mean(1))
        outs.append(o)
    on_rank = spec.rank_mode != "off"
    for s in range(n_seg):                      # decode rows, segment s
        b0, b1 = t0 + s * seg, t0 + (s + 1) * seg
        kk, vv = k[:b1], v[:b1]
        if on_rank:
            kb = k[:b0] * valid[:b0, None, None]
            w = mass[:, :b0] * valid[None, :b0]
            tot = w.sum(-1, keepdims=True)
            w = jnp.where(tot > 0, w / jnp.where(tot > 0, tot, 1.0),
                          valid[None, :b0].astype(F32))
            kr = _round(kb, lo, axis=0)
            gram = jnp.einsum("kgd,gk,kge->gde", kr, w, kr,
                              precision=HIGHEST)
            _, vecs = jnp.linalg.eigh(gram)
            basis = jnp.flip(vecs, -1)[..., :rk]          # (g, dh, rk)
            keep = (jnp.arange(rk) < ranks[s]).astype(F32)
            br = _round(basis, lo, axis=1)
            qd = jnp.einsum("qgrd,gde->qgre", _round(qg[b0:b1], lo),
                            br * keep, precision=HIGHEST)
            kd = jnp.einsum("kgd,gde->kge", _round(kk, lo), br,
                            precision=HIGHEST)
        else:
            qd, kd = qg[b0:b1], kk
        o, p = _attend(qd, kd, vv, idx[b0:b1], idx[:b1], valid[:b1], scale,
                       lo)
        p = p * valid[b0:b1][None, None, :, None]
        mass = mass.at[:, :b1].add(p.sum(2).mean(1))
        outs.append(o)
    o = jnp.concatenate(outs, 0).reshape(T, hq * dh)
    x = x + _mm(o, a["wo"], lo)
    f = lp["ffn"]
    h = _rms(x, lp["ln2"], spec.rms_eps)
    g = _mm(h, f["w_gate"], lo)
    u = _mm(h, f["w_up"], lo)
    return x + _mm(jax.nn.silu(g) * u, f["w_down"], lo)


def final_hidden(spec: Spec, params, prompt, served, ranks, *, t0: int,
                 n_seg: int, qblk: int, lo: Optional[str] = None):
    """Residual stream after the last layer, (T, d) float32, for one
    request. ``ranks`` holds the program's rank per decode segment."""
    P, n = len(prompt), len(served)
    if n_seg * spec.segment_len < n - 1 or t0 < P:
        raise ValueError("t0 / n_seg too small for this request")
    T = t0 + n_seg * spec.segment_len
    idx = np.arange(T)
    pos = np.maximum(idx - (t0 - P), 0).astype(np.int32)
    valid = (idx >= t0 - P) & (idx < t0 + n - 1)
    toks = np.zeros(T, np.int32)
    toks[t0 - P:t0] = prompt
    toks[t0:t0 + n - 1] = served[:-1]
    rk = np.full(n_seg, spec.r_keep, np.int32)
    m = min(len(ranks), n_seg)
    rk[:m] = np.asarray(ranks[:m], np.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(toks)].astype(F32)
        lay = params["layers"]
        for li in range(spec.layers):
            lp = jax.tree.map(lambda a, i=li: a[i], lay)
            x = _layer(lp, x, jnp.asarray(pos), jnp.asarray(valid),
                       jnp.asarray(rk), spec=spec, t0=t0, n_seg=n_seg,
                       qblk=qblk, lo=lo)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "lo"))
def _head_block(ln_f, head, x, x_ctl, served, *, eps, lo):
    """Per row: reference best logit, reference logit of the served token,
    and reference logit of the token the control ranks first."""
    def logits(xx, lo_):
        return _mm(_rms(xx, ln_f, eps), head, lo_)
    ref = logits(x, None)
    best = ref.max(-1)
    at_served = jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
    if x_ctl is None:
        return best, at_served, at_served
    ctl_top = jnp.argmax(logits(x_ctl, lo), -1)
    at_ctl = jnp.take_along_axis(ref, ctl_top[:, None], 1)[:, 0]
    return best, at_served, at_ctl


def gaps(spec: Spec, params, x, served, *, t0: int, x_ctl=None,
         lo: Optional[str] = None, rows: int = 512):
    """Per served token: reference best logit minus the reference logit of
    the served token (and, with a control, of the control's first token).
    Returns float64 arrays (n,) and (n,) or None."""
    n = len(served)
    out_s, out_c = [], []
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, n, rows):
            r1 = min(n, r0 + rows)
            sl = slice(t0 - 1 + r0, t0 - 1 + r1)
            pad = rows - (r1 - r0)
            xb = jnp.pad(x[sl], ((0, pad), (0, 0)))
            xc = None if x_ctl is None else jnp.pad(x_ctl[sl],
                                                    ((0, pad), (0, 0)))
            sv = jnp.asarray(np.pad(np.asarray(served[r0:r1], np.int32),
                                    (0, pad)))
            best, at_s, at_c = jax.device_get(_head_block(
                params["ln_f"], params["lm_head"], xb, xc, sv,
                eps=spec.rms_eps, lo=lo))
            out_s.append((best - at_s)[:r1 - r0])
            out_c.append((best - at_c)[:r1 - r0])
    g = np.concatenate(out_s).astype(np.float64)
    c = np.concatenate(out_c).astype(np.float64)
    return g, (c if x_ctl is not None else None)
