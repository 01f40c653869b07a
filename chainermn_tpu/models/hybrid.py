"""A decoder-only LM whose depth is a string of layer kinds — the
Nemotron-H family's shape: every layer is ``h <- h + mixer(RMSNorm(h))`` with
ONE mixer, its kind read from ``layer_kinds[i]``:

* ``M`` — a Mamba-2 mixer: one input projection to ``[z | xBC | dt]``, a
  causal depthwise convolution and SiLU over ``xBC``, the state-space
  recurrence through :func:`~chainermn_tpu.ops.ssd_scan.ssd_scan`, a gated
  grouped RMS norm and the output projection;
* ``*`` — causal grouped-query attention with a free ``head_dim`` and no
  positional encoding (the Mamba layers carry position), on the flash kernel;
* ``E`` — a mixture of experts: sigmoid router over all
  ``experts_held * ep_of`` experts, the ``experts_held`` this chip holds
  through :func:`~chainermn_tpu.parallel.held_experts.held_experts_ffn`
  (dropless), plus a shared expert every token takes; ``relu(.)**2`` MLPs.
  The routed experts elsewhere are left out of the sum: ``ep_of = 1`` holds
  them all.

RMS norms, no bias anywhere but the convolution's, an untied bias-free head.
Trains through :func:`~chainermn_tpu.models.lm_loss_chunked` like
:class:`~chainermn_tpu.models.TransformerLM` (``return_hidden=True``, the
head read from ``lm_head/kernel``); each block is under ``jax.checkpoint``
when ``remat``.  Training only: no cache, no decode path.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from chainermn_tpu.ops.flash_attention import (
    flash_attention,
    reference_attention,
    resolve_attention,
)
from chainermn_tpu.ops.ssd_scan import causal_depthwise_conv, ssd_scan
from chainermn_tpu.parallel.held_experts import (
    held_experts_ffn,
    held_range,
    relu2,
    sigmoid_topk_route,
)

LAYER_KINDS = "M*E"

def router_bias(layer: int, n_experts: int) -> jax.Array:
    """The ``e_bias`` buffer of layer ``layer``: a constant outside the
    parameter tree (the published models update it by a balancing rule that
    is no part of the loss; here it is a fixed small offset that moves the
    choice without swamping the scores)."""
    j = jnp.arange(n_experts, dtype=jnp.float32)
    return 0.02 * jnp.sin(0.5 + 1.7 * layer + 2.3 * j)


def rms_norm(x, scale, eps: float, groups: int = 1):
    """float32 RMS norm over the last axis, in ``groups`` equal parts."""
    with jax.named_scope("rms_norm"):
        x = x.astype(jnp.float32)
        g = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
        return g.reshape(x.shape) * scale.astype(jnp.float32)


class _HybridBlock(nn.Module):
    kind: str
    layer: int
    cfg: Any  # the HybridLM's fields (:class:`_Fields`): the geometry

    def _dense(self, features, name, axis=-1):
        c = self.cfg
        return nn.DenseGeneral(features, axis=axis, use_bias=False,
                               dtype=c.dtype, param_dtype=c.param_dtype,
                               kernel_init=nn.initializers.normal(0.02),
                               name=name)

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        scale = self.param("norm", nn.initializers.ones, (c.d_model,),
                           c.param_dtype)
        u = rms_norm(h, scale, c.norm_eps)
        mixer = {"M": self._mamba, "*": self._attention, "E": self._experts}
        return h + mixer[self.kind](u).astype(h.dtype)

    # ------------------------------------------------------------ M
    def _mamba(self, u):
        c = self.cfg
        B, T, _ = u.shape
        H, P, G, N = c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state
        inner, bc = H * P, G * N
        with jax.named_scope("ssm.in_proj"):
            zxbcdt = self._dense(2 * inner + 2 * bc + H, "in_proj")(
                u.astype(c.dtype))
            z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], -1)
        with jax.named_scope("ssm.conv"):
            kernel = self.param("conv_kernel", nn.initializers.normal(0.02),
                                (c.conv_kernel, inner + 2 * bc), c.param_dtype)
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (inner + 2 * bc,), c.param_dtype)
            xbc = jax.nn.silu(causal_depthwise_conv(
                xbc, kernel.astype(c.dtype), bias.astype(c.dtype)))
            x, Bm, Cm = jnp.split(xbc, [inner, inner + bc], -1)
        with jax.named_scope("ssm.scan"):
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (H,),
                                 c.param_dtype)
            A_log = self.param("A_log", nn.initializers.zeros, (H,),
                               c.param_dtype)
            D = self.param("D", nn.initializers.ones, (H,), c.param_dtype)
            delta = jax.nn.softplus(dt.astype(jnp.float32)
                                    + dt_bias.astype(jnp.float32))
            y = ssd_scan(x.reshape(B, T, H, P), delta,
                         -jnp.exp(A_log.astype(jnp.float32)),
                         Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N),
                         chunk=min(c.ssm_chunk, T), D=D)
        with jax.named_scope("ssm.gate_out"):
            gate = self.param("gate_norm", nn.initializers.ones, (inner,),
                              c.param_dtype)
            y = y.reshape(B, T, inner) * jax.nn.silu(z.astype(jnp.float32))
            y = rms_norm(y, gate, c.norm_eps, groups=G)
            return self._dense(c.d_model, "out_proj")(y.astype(c.dtype))

    # ------------------------------------------------------------ *
    def _attention(self, u):
        c = self.cfg
        T = u.shape[1]
        u = u.astype(c.dtype)
        with jax.named_scope("attn_qkv"):
            q = self._dense((c.n_heads, c.head_dim), "q")(u)
            kv = self._dense((2, c.n_kv_heads, c.head_dim), "kv")(u)
            k, v = kv[:, :, 0], kv[:, :, 1]
        if resolve_attention(c.attention, T) == "flash":
            with jax.named_scope("attn.flash"):
                a = flash_attention(q, k, v, causal=True)
        else:
            with jax.named_scope("attn.xla"):
                a = reference_attention(q, k, v, causal=True).astype(q.dtype)
        with jax.named_scope("attn_out"):
            return self._dense(c.d_model, "proj", axis=(-2, -1))(a)

    # ------------------------------------------------------------ E
    def _experts(self, u):
        c = self.cfg
        B, T, D = u.shape
        n_all = c.experts_held * c.ep_of
        lo, _ = held_range(c.ep_index, c.ep_of, c.experts_held)
        flat = u.reshape(B * T, D)
        x = flat.astype(c.dtype)
        init = nn.initializers.normal(0.02)
        with jax.named_scope("moe.route"):
            w_gate = self.param("router", init, (D, n_all), c.param_dtype)
            experts, weights = sigmoid_topk_route(
                flat, w_gate, router_bias(self.layer, n_all),
                c.experts_per_tok, scale=c.routed_scale)
        w_up = self.param("experts_up", init,
                          (c.experts_held, D, c.d_expert), c.param_dtype)
        w_down = self.param("experts_down", init,
                            (c.experts_held, c.d_expert, D), c.param_dtype)
        # The buffer of the usual case: three times the rows the held
        # experts draw on average.  Rows of one text repeat their tokens (a
        # row of the benchmark's traffic has ~350 distinct ones, the
        # commonest a sixth of it), so a layer near the embedding can send
        # this shard twice its share; past the bound the layer works over
        # all pairs, 1% of a step slower, so the bound sits where a seed
        # does not reach it (PERF.md §6, PR 38).
        routed, counters = held_experts_ffn(
            x, experts, weights, w_up, w_down, lo=lo,
            tile=min(128, 8 * -(-B * T // 8)),
            row_bound=3 * B * T * c.experts_per_tok // c.ep_of)
        for name, value in counters.items():
            self.sow("intermediates", name, value)
        with jax.named_scope("moe.shared"):
            y = self._dense(D, "shared_down")(
                relu2(self._dense(c.d_shared, "shared_up")(x)))
        with jax.named_scope("moe.combine"):
            return (routed + y.astype(jnp.float32)).reshape(B, T, D)


@dataclasses.dataclass(frozen=True)
class _Fields:
    """A :class:`HybridLM`'s own fields as plain data (a block cannot hold
    its parent module as an attribute)."""

    values: tuple

    def __getattr__(self, name):
        for key, value in self.values:
            if key == name:
                return value
        raise AttributeError(name)


class HybridLM(nn.Module):
    """See the module docstring.  ``layer_kinds`` has one letter a layer;
    the model runs its first ``n_layers``."""

    vocab: int
    n_layers: int
    d_model: int
    layer_kinds: str
    # * attention
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 64
    attention: str = "auto"
    # M state-space mixer
    ssm_heads: int = 8
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_chunk: int = 128
    conv_kernel: int = 4
    # E experts: ``experts_held`` of ``experts_held * ep_of`` live here, the
    # contiguous range of shard ``ep_index``
    experts_held: int = 8
    ep_of: int = 1
    ep_index: int = 0
    experts_per_tok: int = 2
    routed_scale: float = 1.0
    d_expert: int = 256
    d_shared: int = 256
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: each block under ``jax.checkpoint``: O(n_layers) residuals only
    remat: bool = False

    #: what every ``E`` layer sows and :func:`lm_loss_chunked` reports in
    #: the step's metrics, each with how the layers' values are merged
    routing_counters = {"moe_pairs_held": jnp.mean,
                        "moe_rows_max_over_mean": jnp.max,
                        "moe_pairs_dropped": jnp.sum}

    @nn.compact
    def __call__(self, tokens, segment_ids=None, return_hidden: bool = False):
        if segment_ids is not None:
            raise NotImplementedError(
                "HybridLM trains whole rows: packed documents would need the "
                "scan's state and the convolution reset at each boundary")
        kinds = self.layer_kinds[:self.n_layers]
        if len(kinds) != self.n_layers or set(kinds) - set(LAYER_KINDS):
            raise ValueError(
                f"layer_kinds={self.layer_kinds!r}: need {self.n_layers} "
                f"letters of {LAYER_KINDS!r}")
        with jax.named_scope("embed"):
            h = nn.Embed(self.vocab, self.d_model, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="embed")(tokens)
        fields = _Fields(tuple(
            (f.name, getattr(self, f.name)) for f in dataclasses.fields(self)
            if f.name not in ("parent", "name")))
        block = nn.remat(_HybridBlock) if self.remat else _HybridBlock
        for i, kind in enumerate(kinds):
            h = block(kind=kind, layer=i, cfg=fields, name=f"block_{i}")(h)
        scale = self.param("norm_f", nn.initializers.ones, (self.d_model,),
                           self.param_dtype)
        h = rms_norm(h, scale, self.norm_eps).astype(self.dtype)
        if return_hidden:
            return h
        with jax.named_scope("head"):
            return nn.Dense(self.vocab, use_bias=False, dtype=jnp.float32,
                            param_dtype=self.param_dtype, name="lm_head")(h)
