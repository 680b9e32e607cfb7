"""Mixture-of-Experts FFN for the GPT family — beyond the reference.

FleetX has no expert parallelism anywhere (SURVEY.md §2.3: "EP/MoE absent").
This is the GPT block's own expert layer (``Model.moe_num_experts > 0``):
GShard/Switch-style top-k routing expressed entirely as dense einsums over
a capacity-bounded dispatch tensor, so GSPMD shards it like any other
computation. It is the small-scale layer: the ``[tokens, experts,
capacity]`` dispatch grows with all three and overflow is DROPPED. A model
whose experts outnumber the chips, that routes without drops or that holds
a share of its experts uses ``models/mla_moe/moe.py`` instead (sorted rows,
grouped products over the experts held, a sigmoid router with a
load-stepped bias, a shared expert); the two layers share nothing but the
``expert`` logical axis.

- expert weights carry the ``expert`` logical axis, which the layout maps
  to the ``tensor`` mesh axis (``parallel/rules.py:SpecLayout``; ``mlp``
  maps there too, so on an expert leaf ``expert`` takes it and ``mlp``
  replicates): the dispatch/combine einsums become the all-to-alls.
- the router runs in f32; its kernel is ``[embed, experts]`` with no
  sharded dim below ZeRO stage 3 (``gpt_moe`` rule ``mlp/router_kernel``).
- the load-balance auxiliary loss (Switch: ``E * Σ_e f_e·P_e``) is sown
  into the ``losses`` collection; ``GPTModule.training_loss`` adds it,
  eval ignores it.

Tokens beyond an expert's capacity ``C = ceil(cf · k · T / E)`` are dropped
(contribute zero from that expert) — standard capacity-factor semantics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from fleetx_tpu.observability.trace import device_scope

param_with_axes = nn.with_logical_partitioning
with_logical = nn.with_logical_constraint


class MoEMlp(nn.Module):
    """Drop-in replacement for the dense FFN (``GPTMlp``)."""

    cfg: "GPTConfig"  # noqa: F821 — GPTConfig (avoids a circular import)

    @nn.compact
    def __call__(self, x: jax.Array,
                 aux_gate: jax.Array | None = None) -> jax.Array:
        cfg = self.cfg
        E, k = cfg.moe_num_experts, cfg.moe_top_k
        b, s, h = x.shape
        t = b * s
        m = cfg.ffn_dim
        init = nn.initializers.normal(stddev=cfg.initializer_range)

        router = self.param("router_kernel",
                            param_with_axes(init, ("embed", None)),
                            (h, E), jnp.float32)
        wi = self.param("wi_kernel",
                        param_with_axes(init, ("expert", "embed", "mlp")),
                        (E, h, m), cfg.param_dtype)
        bi = self.param("wi_bias",
                        param_with_axes(nn.initializers.zeros, ("expert", "mlp")),
                        (E, m), cfg.param_dtype)
        wo = self.param("wo_kernel",
                        param_with_axes(init, ("expert", "mlp", "embed")),
                        (E, m, h), cfg.param_dtype)
        bo = self.param("wo_bias",
                        param_with_axes(nn.initializers.zeros, ("expert", None)),
                        (E, h), cfg.param_dtype)

        with device_scope("moe.route"):
            x_flat = x.reshape(t, h)
            logits = jnp.einsum("th,he->te", x_flat.astype(jnp.float32), router)
            probs = jax.nn.softmax(logits, axis=-1)

            gate_vals, gate_idx = jax.lax.top_k(probs, k)      # [t, k]
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(axis=-1, keepdims=True), 1e-9)

            capacity = int(max(1, -(-cfg.moe_capacity_factor * k * t // E)))
            onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [t, k, E]
            # GShard priority: all first choices queue before any second choice
            flat = onehot.transpose(1, 0, 2).reshape(k * t, E)
            pos = jnp.cumsum(flat, axis=0) - flat                    # [k*t, E]
            pos = jnp.einsum("fe,fe->f", pos, flat)                  # slot per row
            pos = pos.reshape(k, t).transpose(1, 0).astype(jnp.int32)  # [t, k]
            keep = pos < capacity
            slot = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity,
                                  dtype=jnp.float32)                 # [t, k, C]
            dispatch = jnp.einsum("tke,tkc->tec", onehot,
                                  slot * keep[..., None])            # [t, E, C]
            combine = jnp.einsum("tke,tkc,tk->tec", onehot,
                                 slot * keep[..., None], gate_vals)

            expert_in = jnp.einsum("tec,th->ech",
                                   dispatch.astype(cfg.dtype), x_flat.astype(cfg.dtype))
        with device_scope("moe.experts"):
            expert_in = with_logical(expert_in, ("act_expert", None, "act_embed"))
            h1 = jnp.einsum("ech,ehm->ecm", expert_in, wi.astype(cfg.dtype))
            h1 = h1 + bi.astype(cfg.dtype)[:, None, :]
            h1 = nn.gelu(h1, approximate=True)
            out_e = jnp.einsum("ecm,emh->ech", h1, wo.astype(cfg.dtype))
            out_e = out_e + bo.astype(cfg.dtype)[:, None, :]
        with device_scope("moe.route"):
            y = jnp.einsum("tec,ech->th", combine.astype(cfg.dtype), out_e)

            # Switch load-balance loss: E * Σ_e f_e·P_e (f: dispatched
            # first-choice fraction, P: mean router prob)
            f_e = onehot[:, 0, :].mean(axis=0)
            p_e = probs.mean(axis=0)
            aux = (E * jnp.sum(f_e * p_e)).astype(jnp.float32)
            if aux_gate is not None:
                # Pipeline mode (aux gate from the caller, model.py): GPipe
                # bubble iterations run this routing on zero blocks whose
                # outputs are dropped — zero their aux contribution. The
                # surviving per-microbatch values are averaged back to one
                # batch statistic by GPTModule.training_loss (the standard
                # GShard/Switch semantics under microbatching; it equals the
                # full-batch statistic up to inter-microbatch covariance of
                # f_e and P_e, which is zero at init and stays negligible).
                aux = aux * aux_gate
        self.sow("losses", "moe_aux", cfg.moe_aux_weight * aux)

        return y.reshape(b, s, h)
