"""Plain float32 reference of an SPT language model: its fine-tuning loss
in straightforward ``jax.numpy``, and what the benchmark needs to know of
the model to start the program and the reference from the same weights.

It imports nothing of the program under test. It follows the SPT paper
(sparse MHA over PQ-scored top-L keys, routed FFN over G' of G weight
blocks, LoRA adapters) and the semantics that the program documents:

* PQ codes: each d'-wide sub-vector of a query or key takes its nearest
  codeword (squared L2, the lowest index on a tie). A query-key score is
  the number of books whose codes match.
* Selection: each query keeps its ``budget`` keys of highest score among
  the keys it may see, the most recent first on a tie; all of them when it
  sees fewer. Softmax runs over the kept keys only.
* Routed FFN: a token takes the G' groups of largest |router logit|. Per
  sequence, the (token, choice) pairs fill each group's ``capacity`` in
  token order, and a pair past it is dropped. A kept group adds
  act(x W_I[g]) W_O[g], with LoRA on each projection.
* The loss is the mean next-token cross-entropy plus the switch-style
  load-balance term, weighted and averaged over layers. AdamW
  (``common.run_train_steps``) updates the trainable leaves (LoRA, router,
  codebooks) after clipping by the global gradient norm.

Every matrix product goes through ``common.Dots``: float32 at "highest"
precision for the reference, or operands rounded to float8 for the control.

A configuration file names this module as its ``"reference"``. What a
reference module supplies: ``model_from``, ``train_loss``,
``PROGRAM_FIELDS``, ``weight_std``, ``weight_const``, ``reference_path``
and ``per_layer``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from bench import weights
from bench.reference.common import F32, Dots, combine

Path = Tuple[str, ...]


# ------------------------------------------------------------ config
@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes and choices the reference needs, named for the reference."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab_rows: int            # rows of the embedding table the logits span
    norm: str                  # rmsnorm | layernorm
    qk_norm: bool
    rope_theta: Optional[float]
    learned_pos: bool
    gated: bool
    activation: str            # silu | relu | gelu
    tie_embeddings: bool
    code_dim: int
    codewords: int
    top_fraction: float
    min_l: int
    pad_l_to: int
    groups: int
    active_groups: int
    capacity_factor: float
    capacity_pad: int
    lora_rank: int
    lora_alpha: float
    lb_weight: float
    norm_eps: float = 1e-6

    @property
    def books(self) -> int:
        return self.head_dim // self.code_dim

    @property
    def lora_scale(self) -> float:
        return self.lora_alpha / self.lora_rank


def top_l(horizon: int, m: Model) -> int:
    """Keys a query keeps out of ``horizon`` (the paper's L = n / 8)."""
    l = max(m.min_l, int(round(horizon * m.top_fraction)))
    l = -(-l // m.pad_l_to) * m.pad_l_to
    return min(l, horizon)


def capacity(tokens: int, m: Model) -> int:
    """(token, choice) pairs a group takes per sequence."""
    pad = max(8, m.capacity_pad)
    c = int(tokens * m.active_groups * m.capacity_factor / m.groups) + 1
    c = -(-c // pad) * pad
    return min(c, max(pad, -(-tokens * m.active_groups // pad) * pad))


# ------------------------------------------------------------ layers
def _norm(x, p, m: Model):
    if m.norm == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + m.norm_eps) * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + m.norm_eps) * p["scale"] + p["bias"]


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x (B, H, S, hd), pos (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) * 2 / x.shape[-1])
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(m: Model):
    return {"silu": jax.nn.silu, "relu": jax.nn.relu,
            "gelu": jax.nn.gelu}[m.activation]


def _linear(dot, x, p, m: Model):
    """x W + s (x B) C."""
    y = dot("...d,dh->...h", x, p["w"])
    return y + m.lora_scale * dot("...r,rh->...h",
                                  dot("...d,dr->...r", x, p["b"]), p["c"])


def pq_codes(x, codebooks):
    """(..., n, M*d') -> (..., n, M) nearest codeword of each sub-vector."""
    books, _, dp = codebooks.shape
    xs = x.reshape(*x.shape[:-1], books, 1, dp)
    dist = jnp.sum((xs - codebooks) ** 2, axis=-1)          # (..., n, M, E)
    return jnp.argmin(dist, axis=-1)


def kept_keys(cq, ck, visible, budget):
    """Which keys each query keeps.

    cq (B, H, nq, M), ck (B, H, nk, M) codes; visible (B|1, 1, nq, nk);
    budget (B|1, 1, nq, 1) keys per query. Score = matching books; ties go
    to the more recent key."""
    nk = ck.shape[2]
    score = jnp.sum(cq[:, :, :, None, :] == ck[:, :, None, :, :], axis=-1)
    rank = score.astype(F32) * nk + jnp.arange(nk, dtype=F32)  # distinct
    rank = jnp.where(visible, rank, -1.0)
    order = jnp.sort(rank, axis=-1)[..., ::-1]                 # descending
    kth = jnp.take_along_axis(order, jnp.clip(budget - 1, 0, nk - 1), -1)
    return visible & (rank >= jnp.maximum(kth, 0.0))


def attention(dot, p, x, m: Model, budget_of, q_chunk: int = 256):
    """Causal sparse MHA of one layer over x (B, S, d).

    budget_of(q_pos (nq,)) -> (B|1, 1, nq, 1) keys each query keeps."""
    b, s, _ = x.shape
    hq, hk, hd = m.heads, m.kv_heads, m.head_dim
    r = hq // hk

    def heads(y, n):
        return y.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = heads(_linear(dot, x, p["wq"], m), hq)
    k = heads(_linear(dot, x, p["wk"], m), hk)
    v = heads(_linear(dot, x, p["wv"], m), hk)
    if m.qk_norm:
        q = _rms(q, p["q_norm"], m.norm_eps)
        k = _rms(k, p["k_norm"], m.norm_eps)
    pos = jnp.arange(s)
    if m.rope_theta is not None:
        q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
    k = jnp.repeat(k, r, axis=1)                # each query head's keys
    v = jnp.repeat(v, r, axis=1)
    cb = jax.lax.stop_gradient(p["codebooks"])
    cq = pq_codes(jax.lax.stop_gradient(q), cb)
    ck = pq_codes(jax.lax.stop_gradient(k), cb)
    outs = []
    for start in range(0, s, q_chunk):
        qp = pos[start:start + q_chunk]
        visible = (pos[None, :] <= qp[:, None])[None, None]
        keep = kept_keys(cq[:, :, start:start + q_chunk], ck, visible,
                         budget_of(qp))
        logits = dot("bhqd,bhkd->bhqk", q[:, :, start:start + q_chunk],
                     k) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        outs.append(dot("bhqk,bhkd->bhqd", jnp.where(keep, w, 0.0), v))
    o = jnp.concatenate(outs, axis=2).transpose(0, 2, 1, 3).reshape(
        b, s, hq * hd)
    return _linear(dot, o, p["wo"], m)


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def routed_ffn(dot, p, x, m: Model, cap_tokens):
    """Routed FFN of one layer over x (B, S, d); returns (y, lb_loss).

    cap_tokens: the sequence length the capacity is sized for; None for no
    capacity limit (one token at a time, as in decoding); or (capacity,
    capped (B, S)) where only the capped positions fill the capacity."""
    b, s, d = x.shape
    g = m.groups
    logits = dot("bsd,dg->bsg", x, p["router"])
    _, choice = jax.lax.top_k(jnp.abs(logits), m.active_groups)
    onehot = jax.nn.one_hot(choice, g, dtype=jnp.int32)       # (B,S,K,G)
    if cap_tokens is None:
        kept = onehot
    else:
        if isinstance(cap_tokens, tuple):          # (capacity, capped (B,S))
            cap, capped = cap_tokens
        else:
            cap, capped = capacity(cap_tokens, m), jnp.ones((b, s), bool)
        capped = jnp.broadcast_to(capped[:, :, None, None], onehot.shape)
        flat = (onehot * capped).reshape(b, s * m.active_groups, g)
        rank = jnp.cumsum(flat, axis=1) - flat
        over = (flat * (rank >= cap)).reshape(onehot.shape)
        kept = onehot - over
    mask = jnp.sum(kept, axis=2).astype(F32)                   # (B, S, G)
    frac = jnp.mean(jnp.sum(onehot, 2).astype(F32), (0, 1)) / m.active_groups
    prob = jnp.mean(jax.nn.softmax(logits, -1), (0, 1))
    lb = g * jnp.sum(frac * prob)

    def up(w, lo):
        y = dot("bsd,gdf->bsgf", x, w)
        return y + m.lora_scale * dot("bsr,grf->bsgf",
                                      dot("bsd,dr->bsr", x, lo["b"]), lo["c"])

    h = up(p["w_inner"], p["lora_inner"])
    h = _act(m)(up(p["w_gate"], p["lora_gate"])) * h if m.gated \
        else _act(m)(h)
    h = h * mask[..., None]
    y = dot("bsgf,gfd->bsd", h, p["w_outer"])
    lo = p["lora_outer"]
    y = y + m.lora_scale * dot("bsr,rd->bsd",
                               dot("bsgf,gfr->bsr", h, lo["b"]), lo["c"])
    return y, lb


def _embed(params, tokens, m: Model):
    x = params["embed"][tokens].astype(F32)
    if m.learned_pos:
        x = x + params["pos"][jnp.arange(tokens.shape[1])].astype(F32)
    return x


def _head(params, m: Model):
    return params["embed"].T if m.tie_embeddings else params["head"]


def _up(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def hidden(params, tokens, m: Model, dot: Dots, budget_of, cap_tokens):
    """Final-normed hidden states (B, S, d) and the summed load-balance loss.
    Layers run under a scan, each recomputed in the backward pass."""
    def layer(x, lp):
        lp = _up(lp)
        x = x + attention(dot, lp["attn"], _norm(x, lp["norm_mix"], m), m,
                          budget_of)
        y, lb = routed_ffn(dot, lp["ffn"], _norm(x, lp["norm_ffn"], m), m,
                           cap_tokens)
        return x + y, lb

    x, lbs = jax.lax.scan(jax.checkpoint(layer), _embed(params, tokens, m),
                          params["layers"])
    return _norm(x, _up(params["final_norm"]), m), jnp.sum(lbs)


# ------------------------------------------------------------ training
def train_loss(train, frozen, tokens, labels, m: Model, dot: Dots):
    params = combine(train, frozen)
    s = tokens.shape[1]
    budget = top_l(s, m)
    h, lb = hidden(params, tokens, m, dot,
                   lambda qp: jnp.full((1, 1, qp.shape[0], 1), budget), s)
    logits = dot("bsd,dv->bsv", h, _head(params, m).astype(F32))
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    lm = jnp.mean(lse - tgt)
    return lm + m.lb_weight * lb / m.layers


def model_from(cfg: Dict) -> Model:
    """A Model from the configuration file's "model" block."""
    fields = {f.name for f in dataclasses.fields(Model)}
    return Model(**{k: v for k, v in cfg.items() if k in fields})


# ------------------------------------------------------------ the program
def _learned_positions(cfg) -> bool:
    return cfg.positional == "learned"


# reference field -> program field (ModelConfig, or its SPTConfig as spt.*,
# or a function of the ModelConfig)
PROGRAM_FIELDS = {
    "layers": "num_layers", "d_model": "d_model", "heads": "num_heads",
    "kv_heads": "num_kv_heads", "head_dim": "resolved_head_dim",
    "d_ff": "d_ff", "vocab_rows": "padded_vocab", "norm": "norm",
    "qk_norm": "qk_norm", "rope_theta": "rope_theta", "gated": "gated_ffn",
    "activation": "activation", "tie_embeddings": "tie_embeddings",
    "code_dim": "spt.pq_code_dim", "codewords": "spt.pq_codewords",
    "top_fraction": "spt.attn_top_fraction", "min_l": "spt.attn_min_l",
    "pad_l_to": "spt.attn_pad_l_to", "groups": "spt.ffn_groups",
    "active_groups": "spt.ffn_active_groups",
    "capacity_factor": "spt.ffn_capacity_factor",
    "capacity_pad": "spt.dispatch_pad", "lora_rank": "spt.lora.rank",
    "lora_alpha": "spt.lora.alpha", "lb_weight": "spt.lb_loss_weight",
    "learned_pos": _learned_positions,
}


def weight_std(path: Path, shape, model: dict) -> float:
    """Standard deviation of the program's leaf at ``path`` ("train" or
    "frozen" first); 0 for a constant (see ``weight_const``).

    Projections by 1/sqrt(fan-in), embeddings at 0.02, norms at one and
    zero, codebooks at unit scale (the size of a normalised head's
    sub-vector), and the frozen weights that write the residual stream (the
    attention and FFN outputs) further by the configuration's
    ``residual_gain``. SPT's discrete choices (PQ codes, top-L keys, top-G'
    groups) flip under any rounding; with GPT-2 scales, 28 random layers
    amplify the flips until bfloat16 and float32 runs of one model share
    no more than their statistics. A small residual gain (as SkipInit and
    ReZero start) keeps the stack well-conditioned. The residual stream
    then stays near the token's embedding, so the final norm's scale is
    drawn around zero (unit normal): with a tied head, a scale of one would
    make every token predict itself.

    Adapters: the two that write the residual stream start as LoRA starts,
    their up factor at zero; the others are taken mid-run, both factors
    non-zero, so that their every leaf has a gradient at step one."""
    name, parent = path[-1], path[-2] if len(path) > 1 else ""
    residual = model["residual_gain"]
    if name in ("embedding", "pos_embedding"):
        return 0.02
    if name == "codebooks":
        return 1.0
    if parent == "final_norm" and name == "scale":
        return 1.0
    if name in ("scale", "bias") and "norm" in parent:
        return 0.0
    if name == "c" and len(path) > 2:                  # LoRA up factor
        if "wo" in path or "lora_outer" in path:       # writes the residual
            return 0.0
        return 0.1 / math.sqrt(shape[-2])
    if name == "w_outer":
        return residual / math.sqrt(shape[-2] * model["active_groups"])
    if parent == "wo" and name == "w":
        return residual / math.sqrt(shape[-2])
    return 1.0 / math.sqrt(shape[-2])


def weight_const(path: Path) -> float:
    """The value of a leaf whose ``weight_std`` is 0."""
    return 1.0 if path[-1] == "scale" else 0.0


# program layout -> reference layout
_RENAME = {("embed", "embedding"): ("embed",),
           ("pos", "pos_embedding"): ("pos",),
           ("head", "w"): ("head",)}


def reference_path(path: Path) -> Path:
    """('frozen','units','b0_attn','mixer','wq','lora','b') ->
    ('layers','attn','wq','b'), and so on."""
    p = weights.model_path(path)
    if p[:1] == ("units",):
        p = ("layers",) + p[2:]
        p = tuple("attn" if s == "mixer" else s for s in p)
        p = tuple(s for s in p if s not in ("lora", "pq"))
    return _RENAME.get(p, p)


def per_layer(tree: dict, m: Model) -> Dict:
    """A reference-layout tree as per-layer leaves ("layers/3/attn/wq/b")."""
    return weights.layer_leaves(tree, m.layers)
