"""The dense Llama block (``model_type`` ``llama``): the program's
``ModelConfig``, the plain float32 reference, the model FLOPs of a step and
the norm leaves.

The reference follows the published decoder (RMSNorm, rotary embedding
with the half-split rotation, grouped-query causal attention, SwiGLU MLP,
untied head) with LoRA ``y += (x @ A) @ B`` on every adapted linear
(adapters are served at scale 1, alpha / r folded into B).  It imports
nothing of the program: it reads the parameter pytree by leaf name (stacked
over layers on axis 0) and the published sizes from the configuration file.
Every product runs at ``Precision.HIGHEST``.  It walks one layer at a time
and a few rows at a time, so it fits beside nothing else on the chip once
the program's state is freed.

``fp8=True`` is the control: the same arithmetic with every base linear's
input and weight rounded to float8 e4m3 (``reference.fp8_round``).

``train`` follows a fine-tune's first optimizer steps: per-row losses, the
gradient of the weighted loss with respect to one adapter (a reverse pass
layer by layer with ``jax.vjp``), clipped to a global norm, and AdamW with
the adapter's parameters kept in their stored dtype after each step.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import F32, HI, ROWS, add, embed, fp8_round, leaves

NORMS = ("ln1", "ln2", "final_norm")


def model_config(spec: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.configs import ModelConfig
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    return ModelConfig(
        name=spec["name"], arch_type="dense",
        n_layers=spec["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=spec["num_key_value_heads"],
        d_ff=spec["intermediate_size"], vocab=spec["vocab_size"],
        head_dim=d // h, rope_theta=float(spec["rope_theta"]),
        rms_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        dtype=spec["torch_dtype"])


# -- model FLOPs --------------------------------------------------------------

def layer_params(spec: Dict) -> int:
    """Parameters of one decoder layer's linears."""
    d, h, g = (spec["hidden_size"], spec["num_attention_heads"],
               spec["num_key_value_heads"])
    hd, ff = d // h, spec["intermediate_size"]
    return d * h * hd * 2 + d * g * hd * 2 + 3 * d * ff


def lora_params(spec: Dict, r: int) -> int:
    """Parameters of one layer's rank-``r`` adapter on the seven targets."""
    d, h, g = (spec["hidden_size"], spec["num_attention_heads"],
               spec["num_key_value_heads"])
    hd, ff = d // h, spec["intermediate_size"]
    dims = [(d, h * hd), (d, g * hd), (d, g * hd), (h * hd, d),
            (d, ff), (d, ff), (ff, d)]
    return sum(r * (a + b) for a, b in dims)


def step_flops(spec: Dict, r: int, ft_lens: Sequence[int],
               pf_rows: Sequence[Tuple[int, int]],
               dec_ctx: Sequence[int]) -> float:
    """Model FLOPs one unified step needs: 2 per parameter per token through
    the layers (base and adapter), 4 * heads * head_dim per attended key per
    layer, and the head where logits are taken (every fine-tune position,
    the last prompt position of a prefill row, each decode row).  Fine-tune
    tokens count twice: the forward and the input gradient.  ``pf_rows``
    holds (computed tokens, cached prefix); ``dec_ctx`` each decode row's
    context including its new token."""
    L, d, V = (spec["num_hidden_layers"], spec["hidden_size"],
               spec["vocab_size"])
    h = spec["num_attention_heads"]
    lin = 2.0 * (layer_params(spec) + lora_params(spec, r))
    att = 4.0 * h * (d // h)
    head = 2.0 * d * V

    def fwd(tokens, keys, logit_rows):
        return L * (lin * tokens + att * keys) + head * logit_rows

    total = 0.0
    for n in ft_lens:
        total += 2 * fwd(n, n * (n + 1) / 2, n)
    for n, c in pf_rows:
        total += fwd(n, n * c + n * (n + 1) / 2, 1)
    for c in dec_ctx:
        total += fwd(1, c, 1)
    return total


# -- the reference ------------------------------------------------------------

# jitted programs per (width, heads, KV heads, rope theta, eps, fp8):
# every Reference of one configuration shares them, so a process compiles
# them once
_PROGRAMS: Dict[Tuple, Tuple] = {}


class Reference:
    def __init__(self, spec: Dict, *, fp8: bool = False):
        self.d = spec["hidden_size"]
        self.h = spec["num_attention_heads"]
        self.g = spec["num_key_value_heads"]
        self.hd = self.d // self.h
        self.n_layers = spec["num_hidden_layers"]
        self.theta = float(spec["rope_theta"])
        self.eps = float(spec["rms_norm_eps"])
        self.tied = bool(spec.get("tie_word_embeddings", False))
        self.targets = tuple(spec["lora"]["targets"])
        self.fp8 = fp8
        key = (self.d, self.h, self.g, self.theta, self.eps, self.fp8)
        if key not in _PROGRAMS:
            _PROGRAMS[key] = tuple(jax.jit(f) for f in (
                self._layer_fn, self._logits_fn, self._ce_fn,
                self._layer_vjp, self._ce_vjp))
        (self._layer, self._logits, self._ce, self._layer_back,
         self._ce_back) = _PROGRAMS[key]

    # -- arithmetic ---------------------------------------------------------
    def _rms(self, x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + self.eps) * w.astype(F32)

    def _rope(self, x):
        S, hd = x.shape[1], x.shape[-1]
        half = hd // 2
        inv = self.theta ** (-jnp.arange(half, dtype=F32) / half)
        ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def _mm(self, x, w):
        w = w.astype(F32)
        if self.fp8:
            x, w = fp8_round(x, -1), fp8_round(w, 0)
        return jnp.einsum("...i,io->...o", x, w, precision=HI)

    def _layer_fn(self, x, blk, lora, l, ids):
        B, S, _ = x.shape
        H, G, hd = self.h, self.g, self.hd

        def w(k):
            return jax.lax.dynamic_index_in_dim(blk[k], l, 0, keepdims=False)

        def lin(v, k):
            y = self._mm(v, w(k))
            if k in lora:
                a = lora[k]["a"][ids, l].astype(F32)          # [B, din, r]
                b = lora[k]["b"][ids, l].astype(F32)          # [B, r, dout]
                va = jnp.einsum("bsi,bir->bsr", v, a, precision=HI)
                y = y + jnp.einsum("bsr,bro->bso", va, b, precision=HI)
            return y

        hn = self._rms(x, w("ln1"))
        q = self._rope(lin(hn, "wq").reshape(B, S, H, hd))
        k = self._rope(lin(hn, "wk").reshape(B, S, G, hd))
        v = lin(hn, "wv").reshape(B, S, G, hd)
        q = q.reshape(B, S, G, H // G, hd)
        s = jnp.einsum("bsgmd,btgd->bgmst", q, k, precision=HI) * hd ** -0.5
        causal = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bgmst,btgd->bsgmd", p, v,
                       precision=HI).reshape(B, S, H * hd)
        x = x + lin(o, "wo")
        hn = self._rms(x, w("ln2"))
        return x + lin(jax.nn.silu(lin(hn, "wg")) * lin(hn, "wu"), "wd")

    def _layer_vjp(self, x, blk, lora, l, dy):
        """Cotangents of a layer's input and of its one adapter
        (``lora`` stacked over one adapter, on every row) for ``dy``."""
        ids = jnp.zeros((x.shape[0],), jnp.int32)
        _, back = jax.vjp(lambda x, lo: self._layer_fn(x, blk, lo, l, ids),
                          x, lora)
        return back(dy)

    def _ce_vjp(self, h, norm, head, tokens, length, weight):
        """Per-row losses, and the cotangent of ``h`` for the loss
        ``sum(weight * losses)``."""
        ce, back = jax.vjp(
            lambda h: self._ce_fn(h, norm, head, tokens, length), h)
        return ce, back(weight)[0]

    def _head(self, params):
        return params["embed"].T if self.tied else params["lm_head"]

    def _logits_fn(self, h, norm, head, idx):
        """Logits ``[B, P, V]`` at positions ``idx [B, P]`` of ``h``."""
        hp = jnp.take_along_axis(h, idx[..., None], axis=1)
        return self._mm(self._rms(hp, norm), head)

    def _ce_fn(self, h, norm, head, tokens, length):
        """Per-row mean next-token cross-entropy over positions < length-1
        (the program's fine-tune loss)."""
        lg = self._mm(self._rms(h, norm), head)[:, :-1]
        lse = jax.nn.logsumexp(lg, -1)
        tgt = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
        valid = jnp.arange(lg.shape[1])[None, :] < (length[:, None] - 1)
        ce = jnp.where(valid, lse - tgt, 0.0).sum(-1)
        return ce / jnp.maximum(length - 1, 1)

    def _adapted(self, adapters):
        """The adapter factors of the configuration's targets."""
        return {k: v for k, v in adapters["blocks"][0].items()
                if k in self.targets}

    def hidden(self, params, adapters, tokens: np.ndarray,
               adapter_ids: np.ndarray) -> jax.Array:
        """Final hidden states (before the last norm) ``[B, S, d]``."""
        blk = params["blocks"][0]
        lora = self._adapted(adapters)
        x = embed(params["embed"], jnp.asarray(tokens))
        ids = jnp.asarray(adapter_ids, jnp.int32)
        for l in range(self.n_layers):
            x = self._layer(x, blk, lora, jnp.int32(l), ids)
        return x

    # -- what a run compares --------------------------------------------------
    def _blocks(self, items: Sequence[Dict]) -> List[List[Dict]]:
        """``items`` in blocks of ``ROWS``, the last padded with copies of
        its first, so every block has one shape."""
        out = []
        for i in range(0, len(items), ROWS):
            b = list(items[i:i + ROWS])
            out.append(b + [b[0]] * (ROWS - len(b)))
        return out

    def served_logits(self, params, adapters, seqs: Sequence[Dict],
                      s_pad: int, p_pad: int) -> List[np.ndarray]:
        """For each served request ``{"prompt", "output", "adapter"}``, the
        logits ``[len(output), V]`` that predicted each served token: the
        prompt plus the served tokens but the last, one forward pass.
        Sequences pad to ``s_pad`` tokens and outputs to ``p_pad``."""
        out: List[np.ndarray] = []
        for rows in self._blocks(seqs):
            toks = np.zeros((len(rows), s_pad), np.int32)
            idx = np.zeros((len(rows), p_pad), np.int32)
            for j, s in enumerate(rows):
                seq = np.concatenate([s["prompt"], s["output"][:-1]])
                toks[j, :len(seq)] = seq
                n = len(s["output"])
                idx[j, :n] = len(s["prompt"]) - 1 + np.arange(n)
            h = self.hidden(params, adapters, toks,
                            np.array([s["adapter"] for s in rows]))
            lg = np.asarray(self._logits(h, params["final_norm"],
                                         self._head(params),
                                         jnp.asarray(idx)))
            out += [lg[j, :len(s["output"])] for j, s in enumerate(rows)]
        return out[:len(seqs)]

    def grads(self, params, lora, rows: Sequence[Tuple[np.ndarray, float]],
              s_pad: int):
        """Per-row losses of ``rows`` (tokens, loss weight) under the one
        adapter ``lora`` (``{target: {"a", "b"}}`` stacked over one adapter,
        float32), and the gradient of the weighted loss sum with respect to
        it."""
        blk, head = params["blocks"][0], self._head(params)
        ids = jnp.zeros((ROWS,), jnp.int32)
        g = jax.tree_util.tree_map(jnp.zeros_like, lora)
        losses = []
        for i in range(0, len(rows), ROWS):
            rs = rows[i:i + ROWS]
            toks = np.zeros((ROWS, s_pad), np.int32)
            lens = np.ones((ROWS,), np.int32)
            w = np.zeros((ROWS,), np.float32)
            for j, (t, wt) in enumerate(rs):
                toks[j, :len(t)], lens[j], w[j] = t, len(t), wt
            xs = [embed(params["embed"], jnp.asarray(toks))]
            for l in range(self.n_layers):
                xs.append(self._layer(xs[-1], blk, lora, jnp.int32(l), ids))
            ce, dx = self._ce_back(xs.pop(), params["final_norm"], head,
                                   jnp.asarray(toks), jnp.asarray(lens),
                                   jnp.asarray(w))
            losses.append(np.asarray(ce)[:len(rs)])
            for l in reversed(range(self.n_layers)):
                dx, dl = self._layer_back(xs.pop(), blk, lora, jnp.int32(l),
                                          dx)
                g = add(g, dl)
        return np.concatenate(losses), g

    def train(self, params, adapters, index: int,
              steps: Sequence[Sequence[Tuple[np.ndarray, float]]],
              opt: Dict, s_pad: int) -> Dict:
        """The first optimizer steps of a fine-tune of adapter ``index``:
        each step's rows (tokens, loss weight) give per-row losses and the
        gradient of the weighted loss sum, clipped to the global norm
        ``opt["grad_clip"]``; AdamW (``lr``, ``b1``, ``b2``, ``eps``,
        ``weight_decay``) then moves the parameters, which are stored back
        in the adapters' dtype after each step.  Returns ``losses`` (per
        step, per row), ``grad`` (the first clipped gradient) and
        ``change`` (parameters after the steps less before), the last two as
        ``reference.leaves``."""
        src = self._adapted(adapters)
        dtype = jax.tree_util.tree_leaves(src)[0].dtype
        p0 = jax.tree_util.tree_map(lambda x: x[index][None].astype(F32), src)
        p = p0
        m = jax.tree_util.tree_map(jnp.zeros_like, p0)
        v = jax.tree_util.tree_map(jnp.zeros_like, p0)
        b1, b2 = opt["b1"], opt["b2"]
        losses, first = [], None
        for t, rows in enumerate(steps, 1):
            ls, g = self.grads(params, p, rows, s_pad)
            losses.append(ls)
            if opt["grad_clip"] > 0:
                gn = jnp.sqrt(sum(jnp.sum(x * x)
                                  for x in jax.tree_util.tree_leaves(g)))
                c = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
                g = jax.tree_util.tree_map(lambda x: x * c, g)
            if first is None:
                first = g
            m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                       m, g)
            v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                       v, g)

            def move(p, m, v, t=t):
                step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                              + opt["eps"])
                step = step + opt["weight_decay"] * p
                return (p - opt["lr"] * step).astype(dtype).astype(F32)
            p = jax.tree_util.tree_map(move, p, m, v)
        change = jax.tree_util.tree_map(lambda a, b: (a - b)[0], p, p0)
        return dict(losses=losses,
                    grad=leaves(jax.tree_util.tree_map(lambda x: x[0], first),
                                self.targets),
                    change=leaves(change, self.targets))
