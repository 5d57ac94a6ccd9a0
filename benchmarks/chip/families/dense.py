"""The dense decoder family: pre-norm GQA attention with rotary positions
and a SwiGLU MLP in every block, RMSNorm, an optionally tied head
(smollm-135m's ``LlamaForCausalLM``).

Everything the benchmark knows of this family's keys and leaves lives
here: which configuration keys map onto the program's ``ArchConfig``, the
parameter tree, which active size each trailing axis of a leaf keeps in a
width class, the sub-model's loss (the plain reference's forward pass) and
the FLOPs of a local step.  A configuration file names its family by a
``"family"`` key; a file without one is of this family.

The FLOP count is a copy of the dense-attention part of the program's
analytic model (``launch/costs.macs_per_client``): matmul-level
accounting of the sub-model at the client's width class and section
depths, forward + backward = 3x forward, causal attention at half the
square.  Masked padding and recomputation are not counted: the padded
dense program does more, and that is what ``mfu`` is meant to show.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# configuration file key -> the program's ArchConfig field
_ARCH_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
}

# leaf name inside a block (or a global leaf's name) -> which active size
# each trailing axis keeps
_BLOCKS = {
    "embed": (None, "d"),
    "final_norm/scale": ("d",),
    "lm_head": ("d", None),
    "attn/wq": ("d", "hq"),
    "attn/wk": ("d", "hkv"),
    "attn/wv": ("d", "hkv"),
    "attn/wo": ("hq", "d"),
    "ffn/w_gate": ("d", "f"),
    "ffn/w_up": ("d", "f"),
    "ffn/w_down": ("f", "d"),
    "ln1/scale": ("d",),
    "ln2/scale": ("d",),
}


def program_config(cfg: dict, pcfg):
    """The program's ArchConfig ``pcfg`` with the keys the file lists in
    ``reduced`` replaced; every other size has to agree with the file,
    and the architecture has to be one this family's reference covers."""
    red = set(cfg.get("reduced", []))
    over = {f: cfg[k] for k, f in _ARCH_FIELDS.items() if k in red}
    if over:
        pcfg = pcfg.replace(**over)
    bad = {f: (getattr(pcfg, f), cfg[k]) for k, f in _ARCH_FIELDS.items()
           if getattr(pcfg, f) != cfg[k]}
    if bad:
        raise ValueError(f"the program's {cfg['registry']} differs from "
                         f"{cfg['name']}.json: {bad}")
    if pcfg.layer_pattern != ("attn",) or pcfg.moe or pcfg.act != "silu" \
            or pcfg.norm != "rmsnorm" or pcfg.logit_softcap is not None:
        raise ValueError("the reference covers dense attention blocks only")
    return pcfg


def width_sizes(cfg: dict, w: float) -> dict:
    """Contiguous-prefix active sizes of width class ``w`` (HeteroFL-style
    structured pruning, as FedFA's width flexibility defines it)."""
    if not 0.0 < w <= 1.0:
        raise ValueError(f"width class must be in (0, 1], got {w!r}")
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    kv = max(1, int(round(w * K)))
    return {
        "d_model": D if w >= 1.0 else max(16, int(w * D) // 8 * 8),
        "n_heads": kv * (H // K),
        "n_kv_heads": kv,
        "d_ff": F if w >= 1.0 else max(8, int(w * F) // 8 * 8),
    }


def padded_vocab(cfg: dict) -> int:
    return (cfg["vocab_size"] + 127) // 128 * 128


def shapes(cfg: dict) -> dict:
    """Parameter shapes, as the tree the program consumes."""
    D, F, R = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    H, K, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    Vp = padded_vocab(cfg)
    block = {
        "attn": {"wq": (R, D, H * hd), "wk": (R, D, K * hd),
                 "wv": (R, D, K * hd), "wo": (R, H * hd, D)},
        "ffn": {"w_gate": (R, D, F), "w_up": (R, D, F), "w_down": (R, F, D)},
        "ln1": {"scale": (R, D)},
        "ln2": {"scale": (R, D)},
    }
    tree = {"embed": (Vp, D), "final_norm": {"scale": (D,)},
            "stages": ((block,),)}
    if not cfg["tie_word_embeddings"]:
        tree["lm_head"] = (D, Vp)
    return tree


def axis_sizes(cfg: dict, leaf, width: float) -> Tuple[int, ...]:
    """Active size of each trailing axis of ``leaf`` (a ``weights.Leaf``)
    for a width class."""
    sz = width_sizes(cfg, width)
    hd = cfg["head_dim"]
    act = {"d": sz["d_model"], "f": sz["d_ff"], "hq": sz["n_heads"] * hd,
           "hkv": sz["n_kv_heads"] * hd}
    trail = leaf.shape[1:] if leaf.stacked else leaf.shape
    return tuple(n if a is None else act[a]
                 for n, a in zip(trail, _BLOCKS[leaf.short]))


# ---------------------------------------------------------------------------
# the sub-model: a dense decoder LM
# ---------------------------------------------------------------------------

def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1 + scale)


def _rope(x, theta):
    """Rotary positions over the two halves of each head."""
    S, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs       # (S, h/2)
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(p: Dict[str, jax.Array], tokens: jax.Array, cfg: dict,
         sizes: Dict[str, Tuple[int, ...]]) -> jax.Array:
    """Mean next-token cross entropy of the sub-model ``p`` on
    ``tokens`` (B, S); ``sizes`` gives each leaf's active trailing sizes,
    from which the query and key/value head counts follow."""
    B, S = tokens.shape
    hd = cfg["head_dim"]
    hq, hkv = sizes["attn/wq"][-1] // hd, sizes["attn/wk"][-1] // hd
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    V = cfg["vocab_size"]
    dt = p["embed"].dtype
    x = p["embed"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))
    group = hq // hkv

    def layer(x, w):
        h = _rms(x, w["ln1/scale"], eps)
        q = _rope(_mm(h, w["attn/wq"]).reshape(B, S, hq, hd), theta)
        k = _rope(_mm(h, w["attn/wk"]).reshape(B, S, hkv, hd), theta)
        v = _mm(h, w["attn/wv"]).reshape(B, S, hkv, hd)
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
            * jnp.asarray(hd ** -0.5, dt)
        s = jnp.where(causal, s, jnp.asarray(-1e30, dt))
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HIGHEST)
        x = x + _mm(o.reshape(B, S, hq * hd), w["attn/wo"])
        h = _rms(x, w["ln2/scale"], eps)
        f = jax.nn.silu(_mm(h, w["ffn/w_gate"])) * _mm(h, w["ffn/w_up"])
        return x + _mm(f, w["ffn/w_down"]), None

    layers = {k: v for k, v in p.items() if "/" in k and k not in
              ("final_norm/scale",)}
    x, _ = jax.lax.scan(layer, x, layers)
    x = _rms(x, p["final_norm/scale"], eps)
    head = p["lm_head"][:, :V] if "lm_head" in p else p["embed"][:V].T
    logits = _mm(x, head)[:, :-1]
    lp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def train_flops(cfg: dict, width: float, depths: Sequence[int], batch: int,
                seq: int) -> float:
    """FLOPs of one local step (forward + backward) of one client's
    sub-model on a (batch, seq) batch: 2 x the MACs of
    ``costs.macs_per_client``."""
    sz = width_sizes(cfg, width)
    D, hd = sz["d_model"], cfg["head_dim"]
    H, K, F = sz["n_heads"], sz["n_kv_heads"], sz["d_ff"]
    B, S = float(batch), float(seq)
    proj = 2 * B * S * D * (H + 2 * K) * hd + 2 * B * S * H * hd * D
    attn = 2 * 2 * B * S * (S / 2) * H * hd
    ffn = 2 * 3 * B * S * D * F
    layers = sum(depths)
    fwd = layers * (proj + attn + ffn) + 2 * B * S * D * padded_vocab(cfg)
    return 3.0 * fwd
