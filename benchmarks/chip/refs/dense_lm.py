"""Plain reference of a dense GQA decoder trained with AdamW.

It follows the published llama-style block: RMSNorm, attention with rotary
positions (rotate-half) and grouped KV heads, a SwiGLU MLP, a final RMSNorm
and an output head (or the transposed embedding, where tied).  The loss is
the mean cross-entropy plus ``z_loss`` times the mean squared log-partition.
AdamW clips the global gradient norm, then decays every leaf but those that
the training job lists under ``no_decay``.

Beside the computation it says what the harness asks of a reference
(``refs/__init__.py``): the program's fields for these sizes, the model FLOPs
of a trained token (``flops.train_flops_per_token``, the dense block's count)
and the head widths of the attention call.

It imports nothing of the program under test.  Weights come from the
benchmark's own ``weights.leaf`` and tokens from its own traffic generator.
Parameters are stored in the dtype that the configuration states and Adam
moments in the one that the training job states: that rounding is part of the
configured training, so the reference does it too.  Everything else is float32 with matrix products at
``Precision.HIGHEST``.

It runs layer by layer, after the program's state is freed, so that it fits on
the chip: a step keeps each layer's input and the float32 gradient of every
leaf, and nothing else of its size.  Over several chips the rows of a batch
are split between them, and each Adam moment is split along its first axis
that divides evenly, so that float32 moments fit.  Attention runs one query head at a
time, and the loss one block of positions at a time.  Layer gradients stay
apart, one array per layer, and Adam updates each layer's rows of a stacked
leaf in place.

``mode="fp8"`` is the control: the same computation with the operands of
every projection, MLP and head product rounded to float8 (e4m3, one scale per
tensor) in the forward pass, the gradient passing straight through.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import flops, weights

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)
LOSS_BLOCK = 512  # positions per block of the loss


@dataclass(frozen=True)
class Sizes:
    """The shapes of a dense GQA decoder, in the benchmark's own terms."""

    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_layers: int
    tied: bool
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Sizes":
        d, h = c["hidden_size"], c["num_attention_heads"]
        return cls(
            d_model=d, n_heads=h, n_kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or d // h, d_ff=c["intermediate_size"],
            vocab=c["vocab_size"], n_layers=c["num_hidden_layers"],
            tied=c["tie_word_embeddings"], rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
        )

    @property
    def vocab_rows(self) -> int:
        """Rows of the embedding table as held: padded to a multiple of 128.
        The padded rows take no part in the softmax."""
        return math.ceil(self.vocab / 128) * 128


def layout(s: Sizes) -> dict:
    """Leaf name -> shape.  Layer leaves are stacked on a leading layer axis."""
    D, Hq, Hkv, hd, F, L = (s.d_model, s.n_heads, s.n_kv_heads, s.head_dim,
                            s.d_ff, s.n_layers)
    shapes = {
        "ln1": (D,), "attn/wq": (D, Hq * hd), "attn/wk": (D, Hkv * hd),
        "attn/wv": (D, Hkv * hd), "attn/wo": (Hq * hd, D), "ln2": (D,),
        "mlp/gate": (D, F), "mlp/up": (D, F), "mlp/down": (F, D),
    }
    out = {"embed": (s.vocab_rows, D), "final_norm": (D,)}
    out.update({f"groups/{k}": (L,) + v for k, v in shapes.items()})
    if not s.tied:
        out["head"] = (D, s.vocab_rows)
    return out


def program_fields(s: Sizes) -> dict:
    """The program's ``ModelConfig`` fields that these sizes set, by name."""
    return {"n_layers": s.n_layers, "d_model": s.d_model, "n_heads": s.n_heads,
            "n_kv_heads": s.n_kv_heads, "head_dim": s.head_dim, "d_ff": s.d_ff,
            "vocab_size": s.vocab, "tie_embeddings": s.tied, "rope_theta": s.rope_theta,
            "norm_eps": s.norm_eps}


def attention_dims(s: Sizes) -> tuple:
    """``(n_heads, n_kv_heads, d_qk, d_v)`` of a layer's attention call."""
    return s.n_heads, s.n_kv_heads, s.head_dim, s.head_dim


train_flops_per_token = flops.train_flops_per_token  # the dense block's count


def lr_at(opt: dict, k: int) -> float:
    """Learning rate of step ``k`` (from 0): linear warm-up, then cosine
    decay to ``min_lr_frac`` of the peak at ``total_steps``."""
    lr, w = opt["lr"], opt["warmup_steps"]
    if k < w:
        return lr * min(1.0, (k + 1) / max(w, 1))
    t = min(max((k - w) / max(opt["total_steps"] - w, 1), 0.0), 1.0)
    f = opt["min_lr_frac"]
    return lr * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi * t)))


@jax.custom_vjp
def _f8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_f8.defvjp(lambda x: (_f8(x), None), lambda _, g: (g,))


@dataclass
class Readings:
    """What a run of the first steps shows: the loss of each step, the norm
    of each leaf's first gradient as the optimizer took it, and the norm of
    each leaf's change over all the steps."""

    losses: list
    grad_norms: dict
    change_norms: dict


class Reference:
    def __init__(self, sizes, job: dict, precision: dict, devices, mode: str = "f32"):
        """``job``: the traffic file's ``optimizer`` (with its ``moment_dtype``)
        and ``z_loss``; ``precision``: the configuration's ``param_dtype``."""
        if mode not in ("f32", "fp8"):
            raise ValueError(f"mode {mode!r}")
        self.s, self.mode = sizes, mode
        self.opt, self.z_loss = job["optimizer"], job["z_loss"]
        self.pdtype = jnp.dtype(precision["param_dtype"])
        self.mdtype = jnp.dtype(self.opt["moment_dtype"])
        self.mesh = jax.sharding.Mesh(np.asarray(devices), ("rows",))
        self.rep = NamedSharding(self.mesh, P())
        self.rows = NamedSharding(self.mesh, P("rows"))
        self.shapes = layout(sizes)
        self._layer_j = jax.jit(lambda g, l, x: self._layer(self._slice(g, l), x))
        self._layer_vjp = jax.jit(lambda g, l, x, dy: jax.vjp(
            self._layer, self._slice(g, l), x)[1](dy))
        self._head_j = jax.jit(lambda n, w, x, lb: jax.value_and_grad(
            self._head, argnums=(0, 1, 2))(n.astype(jnp.float32), w.astype(jnp.float32),
                                          x, lb))
        self._embed_j = jax.jit(lambda e, t: e[t].astype(jnp.float32))
        self._embed_grad = jax.jit(self._embed_grad_fn)
        self._sq = jax.jit(lambda g: jnp.sum(jnp.square(g)))
        self._adam = jax.jit(self._adam_fn, static_argnames=("decay", "split"),
                             donate_argnums=(0, 1, 2))
        self._adam_layer = jax.jit(self._adam_layer_fn, static_argnames=("decay", "split"),
                                   donate_argnums=(0, 1, 2))
        self._params = jax.jit(lambda key: {
            n: weights.leaf(key, n, s, self.pdtype) for n, s in self.shapes.items()},
            out_shardings=self.rep)
        self._change = jax.jit(lambda key, params: {
            n: jnp.linalg.norm(params[n].astype(jnp.float32)
                               - weights.leaf(key, n, s, self.pdtype).astype(jnp.float32))
            for n, s in self.shapes.items()})
        self._norms = jax.jit(lambda tree: {
            n: jnp.linalg.norm(a.astype(jnp.float32)) for n, a in tree.items()})

    # ---- the model ---------------------------------------------------------

    def _mm(self, a, b):
        if self.mode == "fp8":
            a, b = _f8(a), _f8(b)
        return jnp.matmul(a, b, precision=HIGHEST)

    def _rms(self, x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.s.norm_eps) * w

    def _rope(self, x):
        hd = x.shape[-1]
        freqs = self.s.rope_theta ** (-jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
        sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def _attention(self, q, k, v):
        """Causal softmax attention, one query head at a time."""
        B, S, Hq, hd = q.shape
        group = Hq // k.shape[2]
        causal = jnp.tril(jnp.ones((S, S), bool))
        qh = jnp.moveaxis(q, 2, 0)  # [Hq, B, S, hd]
        kh = jnp.repeat(jnp.moveaxis(k, 2, 0), group, axis=0)  # the head's KV head
        vh = jnp.repeat(jnp.moveaxis(v, 2, 0), group, axis=0)

        @jax.checkpoint
        def one(args):
            qi, ki, vi = args
            s = jnp.einsum("bqd,bkd->bqk", qi, ki, precision=HIGHEST) * hd**-0.5
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, vi, precision=HIGHEST)

        out = jax.lax.map(one, (qh, kh, vh))  # [Hq, B, S, hd]
        return jnp.moveaxis(out, 0, 2)

    def _slice(self, g, l):
        """Layer ``l``'s leaves of the stacked group leaves ``g``, in float32."""
        return {k: v[l].astype(jnp.float32) for k, v in g.items()}

    def _layer(self, p, x):
        B, S, _ = x.shape
        Hq, Hkv, hd = self.s.n_heads, self.s.n_kv_heads, self.s.head_dim
        h = self._rms(x, p["ln1"])
        q = self._rope(self._mm(h, p["attn/wq"]).reshape(B, S, Hq, hd))
        k = self._rope(self._mm(h, p["attn/wk"]).reshape(B, S, Hkv, hd))
        v = self._mm(h, p["attn/wv"]).reshape(B, S, Hkv, hd)
        x = x + self._mm(self._attention(q, k, v).reshape(B, S, Hq * hd), p["attn/wo"])
        h = self._rms(x, p["ln2"])
        gate = jax.nn.silu(self._mm(h, p["mlp/gate"]))
        return x + self._mm(gate * self._mm(h, p["mlp/up"]), p["mlp/down"])

    def _head(self, final_norm, w, x, labels):
        """Mean loss over all positions, one block of positions at a time."""
        V = self.s.vocab
        wv = w[:V].T if self.s.tied else w[:, :V]  # padded rows take no part
        B, S, D = x.shape
        n = math.gcd(S, LOSS_BLOCK)
        xb = jnp.moveaxis(x.reshape(B, S // n, n, D), 1, 0)
        lb = jnp.moveaxis(labels.reshape(B, S // n, n), 1, 0)

        @jax.checkpoint
        def block(args):
            xi, li = args
            logits = self._mm(self._rms(xi, final_norm), wv)
            lse = jax.nn.logsumexp(logits, axis=-1)
            pick = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
            return jnp.sum(lse - pick), jnp.sum(lse * lse)

        ce, zl = jax.lax.map(block, (xb, lb))
        N = B * S
        return (jnp.sum(ce) + self.z_loss * jnp.sum(zl)) / N

    def _embed_grad_fn(self, tokens, dx, head_grad):
        g = jnp.zeros(self.shapes["embed"], jnp.float32)
        g = g.at[tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))
        return g if head_grad is None else g + head_grad

    # ---- the optimizer -----------------------------------------------------

    def _split(self, shape: tuple) -> NamedSharding:
        """A moment's sharding: along its first axis that the chips divide."""
        n = self.mesh.size
        for i, d in enumerate(shape):
            if d % n == 0:
                return NamedSharding(self.mesh, P(*([None] * i), "rows"))
        return self.rep

    def _update(self, p, m, v, g, scale, lr, step, decay):
        o = self.opt
        b1, b2 = o["beta1"], o["beta2"]
        g = g * scale
        mf = b1 * m.astype(jnp.float32) + (1 - b1) * g
        vf = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
        upd = (mf / (1 - b1**step)) / (jnp.sqrt(vf / (1 - b2**step)) + o["eps"])
        pf = p.astype(jnp.float32)
        if decay:
            upd = upd + o["weight_decay"] * pf
        return (pf - lr * upd).astype(p.dtype), mf.astype(m.dtype), vf.astype(v.dtype)

    def _adam_fn(self, p, m, v, g, scale, lr, step, *, decay, split):
        p, m, v = self._update(p, m, v, g, scale, lr, step, decay)
        keep = jax.lax.with_sharding_constraint
        return keep(p, self.rep), keep(m, split), keep(v, split)

    def _adam_layer_fn(self, p, m, v, g, layer, scale, lr, step, *, decay, split):
        """Adam on layer ``layer``'s rows of the stacked leaves ``p, m, v``."""
        pl, ml, vl = self._update(p[layer], m[layer], v[layer], g, scale, lr, step, decay)
        keep = jax.lax.with_sharding_constraint
        return (keep(p.at[layer].set(pl), self.rep), keep(m.at[layer].set(ml), split),
                keep(v.at[layer].set(vl), split))

    # ---- a run -------------------------------------------------------------

    def _grads(self, params, tokens, labels):
        """Loss, the float32 gradient of every leaf outside the layers, and
        each layer's gradients: forward layer by layer, keeping each layer's
        input, then backward layer by layer."""
        g = {k[len("groups/"):]: v for k, v in params.items() if k.startswith("groups/")}
        x = self._embed_j(params["embed"], tokens)
        xs = []
        for l in range(self.s.n_layers):
            xs.append(x)
            x = self._layer_j(g, l, x)
        w = params["embed" if self.s.tied else "head"]
        loss, (g_norm, g_w, dx) = self._head_j(params["final_norm"], w, x, labels)
        grads = {"final_norm": g_norm}
        if not self.s.tied:
            grads["head"] = g_w
        layers = []
        for l in reversed(range(self.s.n_layers)):
            gl, dx = self._layer_vjp(g, l, xs.pop(), dx)
            layers.insert(0, gl)
        grads["embed"] = self._embed_grad(tokens, dx, g_w if self.s.tied else None)
        return loss, grads, layers

    def run(self, seed: int, batches: list) -> Readings:
        """Train from the seed's weights on ``batches`` (host dicts of
        ``tokens`` and ``labels``, one per step) and read what they show."""
        key = weights.root_key(seed)
        params = self._params(key)
        split = {k: self._split(p.shape) for k, p in params.items()}
        m = {k: jnp.zeros(p.shape, self.mdtype, device=split[k]) for k, p in params.items()}
        v = {k: jnp.zeros(p.shape, self.mdtype, device=split[k]) for k, p in params.items()}
        b1, no_decay = self.opt["beta1"], self.opt["no_decay"]
        losses, grad_norms = [], None
        for k, b in enumerate(batches):
            loss, grads, layers = self._grads(params, jax.device_put(b["tokens"], self.rows),
                                              jax.device_put(b["labels"], self.rows))
            gnorm = math.sqrt(sum(float(self._sq(x)) for x in grads.values())
                              + sum(float(self._sq(x)) for gl in layers for x in gl.values()))
            scale = min(1.0, self.opt["clip_norm"] / max(gnorm, 1e-9))
            lr = lr_at(self.opt, k)
            for n in list(grads):
                params[n], m[n], v[n] = self._adam(params[n], m[n], v[n], grads.pop(n),
                                                   scale, lr, k + 1, decay=n not in no_decay,
                                                   split=split[n])
            for layer, gl in enumerate(layers):
                for name in list(gl):
                    n = f"groups/{name}"
                    params[n], m[n], v[n] = self._adam_layer(
                        params[n], m[n], v[n], gl.pop(name), layer, scale, lr, k + 1,
                        decay=n not in no_decay, split=split[n])
            losses.append(float(loss))
            if k == 0:
                grad_norms = {n: float(a) / (1 - b1) for n, a in self._norms(m).items()}
        change = {n: float(a) for n, a in self._change(key, params).items()}
        return Readings(losses, grad_norms, change)
