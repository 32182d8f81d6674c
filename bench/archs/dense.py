"""A dense RoPE/GQA/SwiGLU decoder: its sizes, weights, engine config,
FLOP count and the reference's math for one block.

``model_of`` refuses what this file does not serve as the configuration
states it: a key it does not know (sparse experts, windowed layers,
...), an activation other than SiLU, tied embeddings, and an RMSNorm
eps other than the one the program applies.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import numpy as np

from bench.reference import mm, rmsnorm, rope
from bench.weights import DTYPES, weight_key

# the program's RMSNorm takes its eps from no config: it applies this one
PROGRAM_RMS_EPS = 1e-6

_READ = ("hidden_size", "intermediate_size", "num_attention_heads",
         "num_key_value_heads", "num_hidden_layers", "vocab_size",
         "rms_norm_eps", "rope_theta", "torch_dtype", "head_dim",
         "hidden_act", "tie_word_embeddings")
_ABOUT = ("name", "arch", "source", "reduced", "assumed", "deployment",
          "engine", "max_position_embeddings")


@dataclasses.dataclass(frozen=True)
class Model:
    """A dense RoPE/GQA/SwiGLU decoder, in the published config's terms."""
    name: str
    arch: str
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_hidden_layers: int
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    dtype: str

    def block_matmul_params(self) -> int:
        d, hd = self.hidden_size, self.head_dim
        attn = 2 * d * self.num_attention_heads * hd \
            + 2 * d * self.num_key_value_heads * hd
        return attn + 3 * d * self.intermediate_size


def model_of(conf: Dict[str, Any]) -> Model:
    for key in conf:
        if key not in _READ + _ABOUT:
            raise ValueError(f"{key!r}: not served by the dense "
                             f"architecture")
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"'hidden_act' {conf['hidden_act']!r}: the dense "
                         f"architecture serves 'silu'")
    if conf.get("tie_word_embeddings", False):
        raise ValueError("'tie_word_embeddings' true: the dense "
                         "architecture serves an untied head")
    if float(conf["rms_norm_eps"]) != PROGRAM_RMS_EPS:
        raise ValueError(f"'rms_norm_eps' {conf['rms_norm_eps']!r}: the "
                         f"program applies {PROGRAM_RMS_EPS!r}")
    if conf["torch_dtype"] not in DTYPES:
        raise ValueError(f"unsupported dtype {conf['torch_dtype']!r}")
    head_dim = conf.get("head_dim")
    if head_dim is None:
        head_dim = conf["hidden_size"] // conf["num_attention_heads"]
    return Model(name=conf["name"], arch=conf["arch"],
                 hidden_size=conf["hidden_size"],
                 intermediate_size=conf["intermediate_size"],
                 num_attention_heads=conf["num_attention_heads"],
                 num_key_value_heads=conf["num_key_value_heads"],
                 head_dim=int(head_dim),
                 num_hidden_layers=conf["num_hidden_layers"],
                 vocab_size=conf["vocab_size"],
                 rms_norm_eps=float(conf["rms_norm_eps"]),
                 rope_theta=float(conf["rope_theta"]),
                 dtype=conf["torch_dtype"])


def make_params(model: Model, seed: int):
    """All weights, on the default device, in ``model.dtype``: dense
    kernels N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales 1."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(model.dtype)
    d, f, v, n = (model.hidden_size, model.intermediate_size,
                  model.vocab_size, model.num_hidden_layers)
    hq = model.num_attention_heads * model.head_dim
    hkv = model.num_key_value_heads * model.head_dim

    def build(key):
        names = ["emb", "head", "wq", "wk", "wv", "wo", "wi", "wg", "wf"]
        ks = dict(zip(names, jax.random.split(key, len(names))))

        def normal(name, shape, std):
            return (jax.random.normal(ks[name], shape, jnp.float32)
                    * std).astype(dt)

        def dense(name, d_in, d_out):
            return {"w": normal(name, (n, d_in, d_out), d_in ** -0.5)}

        ones = jnp.ones((n, d), dt)
        return {
            "embed": {"emb": normal("emb", (v, d), 0.02)},
            "blocks": {
                "ln1": {"scale": ones},
                "attn": {"wq": dense("wq", d, hq), "wk": dense("wk", d, hkv),
                         "wv": dense("wv", d, hkv), "wo": dense("wo", hq, d)},
                "ln2": {"scale": ones},
                "mlp": {"wi": dense("wi", d, f), "wg": dense("wg", d, f),
                        "wo": dense("wf", f, d)},
            },
            "final_norm": {"scale": jnp.ones((d,), dt)},
            "lm_head": {"w": normal("head", (d, v), d ** -0.5)},
        }

    return jax.jit(build)(weight_key(seed))


def engine_config(model: Model, cfg_cls):
    """The program's ``LMConfig`` of this model."""
    import jax.numpy as jnp
    return cfg_cls(name=model.name, n_layers=model.num_hidden_layers,
                   d_model=model.hidden_size,
                   n_heads=model.num_attention_heads,
                   n_kv=model.num_key_value_heads, head_dim=model.head_dim,
                   d_ff=model.intermediate_size, vocab=model.vocab_size,
                   rope_base=model.rope_theta, dtype=jnp.dtype(model.dtype),
                   remat=False)


def request_flops(m: Model, prompt_len: int, served: int) -> float:
    """Model FLOPs of one served request, from shapes alone.

    A request with prompt length P that was served N tokens ran P prompt
    positions and N - 1 decode positions (the last served token is never
    fed back), and the LM head once per served token.  A position t
    (0-based) costs 2 x (the matmul parameters of every block), plus
    attention over the t + 1 keys it sees: 2 x 2 x heads x head_dim x
    (t + 1) per block (scores and the weighted sum).  Not counted:
    rejected drafts, the edge's draft passes, bucket padding, and the
    prefill's logits at prompt positions other than the last.
    """
    positions = prompt_len + max(served - 1, 0)
    linear = 2.0 * m.block_matmul_params() * m.num_hidden_layers * positions
    # sum over t of (t + 1) for t < positions
    keys = positions * (positions + 1) / 2.0
    attn = 4.0 * m.num_attention_heads * m.head_dim * m.num_hidden_layers \
        * keys
    head = 2.0 * m.hidden_size * m.vocab_size * served
    return linear + attn + head


def block(m: Model, bp, x, pos, lat, kv):
    """One decoder block over ``x`` [S, D] at positions ``pos`` [S], its
    weights ``bp`` in float32: every weight on ``lat``'s lattice, every
    matmul input on its activation lattice, K and V through the KV cache
    ``kv``.  Returns (x, the cache's state)."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    s = x.shape[0]
    nh, nkv, hd = m.num_attention_heads, m.num_key_value_heads, m.head_dim
    attn, mlp = bp["attn"], bp["mlp"]

    def w(p):
        return lat.weight(p["w"])

    h = lat.act(rmsnorm(x, bp["ln1"]["scale"], m.rms_norm_eps))
    q = mm(h, w(attn["wq"])).reshape(s, nh, hd)
    k = mm(h, w(attn["wk"])).reshape(s, nkv, hd)
    v = mm(h, w(attn["wv"])).reshape(s, nkv, hd)
    q = rope(q, pos, m.rope_theta)
    k = rope(k, pos, m.rope_theta)
    keys, vals, valid, state = kv(k, v)
    group = nh // nkv
    qg = q.reshape(s, nkv, group, hd)
    logits = jnp.einsum("sngd,lnd->ngsl", qg, keys, precision=hi) \
        / np.sqrt(hd)
    logits = jnp.where(valid[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("ngsl,lnd->sngd", p, vals, precision=hi).reshape(s, nh * hd)
    x = x + mm(lat.act(o), w(attn["wo"]))
    z = lat.act(rmsnorm(x, bp["ln2"]["scale"], m.rms_norm_eps))
    g = mm(z, w(mlp["wi"])) * jax.nn.silu(mm(z, w(mlp["wg"])))
    x = x + mm(lat.act(g), w(mlp["wo"]))
    return x, state
