"""Feed-forward blocks: dense MLP (SwiGLU/GELU) and token-choice MoE.

The MoE uses the sort-based, capacity-bounded dispatch that maps well onto
TPUs (static shapes, grouped einsums over the expert axis).  Expert weights
carry the ``expert`` logical axis so expert-parallelism is just a sharding
rule (experts over the ``model`` mesh axis); the scatter/gather between the
token-sharded and expert-sharded layouts lowers to the all-to-all pattern of
classic EP.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.dist import hints
from repro.nn.module import logical
from repro.nn.layers import _trunc_normal


@dataclasses.dataclass(frozen=True)
class MLP:
    d_model: int
    d_ff: int
    act: str = "swiglu"           # swiglu | gelu
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        std_in = self.d_model ** -0.5
        std_out = self.d_ff ** -0.5
        if self.act == "swiglu":
            return {
                "w_gate": _trunc_normal(k1, (self.d_model, self.d_ff), std_in, self.param_dtype),
                "w_up": _trunc_normal(k2, (self.d_model, self.d_ff), std_in, self.param_dtype),
                "w_down": _trunc_normal(k3, (self.d_ff, self.d_model), std_out, self.param_dtype),
            }
        return {
            "w_in": _trunc_normal(k1, (self.d_model, self.d_ff), std_in, self.param_dtype),
            "w_out": _trunc_normal(k2, (self.d_ff, self.d_model), std_out, self.param_dtype),
        }

    def specs(self):
        if self.act == "swiglu":
            return {"w_gate": logical("embed", "mlp"),
                    "w_up": logical("embed", "mlp"),
                    "w_down": logical("mlp", "embed")}
        return {"w_in": logical("embed", "mlp"), "w_out": logical("mlp", "embed")}

    def __call__(self, params, x):
        cd = self.compute_dtype
        x = x.astype(cd)
        if self.act == "swiglu":
            g = jnp.dot(x, params["w_gate"].astype(cd), preferred_element_type=jnp.float32)
            u = jnp.dot(x, params["w_up"].astype(cd), preferred_element_type=jnp.float32)
            h = (jax.nn.silu(g) * u).astype(cd)
            return jnp.dot(h, params["w_down"].astype(cd),
                           preferred_element_type=jnp.float32).astype(cd)
        h = jax.nn.gelu(jnp.dot(x, params["w_in"].astype(cd),
                                preferred_element_type=jnp.float32)).astype(cd)
        return jnp.dot(h, params["w_out"].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)


@dataclasses.dataclass(frozen=True)
class MoEFFN:
    """Token-choice top-k MoE with SwiGLU experts + optional shared experts."""

    d_model: int
    cfg: MoEConfig
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32

    @property
    def capacity_factor(self):
        return self.cfg.capacity_factor

    def init(self, key):
        c = self.cfg
        keys = jax.random.split(key, 5)
        std_in = self.d_model ** -0.5
        std_out = c.d_expert ** -0.5
        E = c.n_experts
        p = {
            "router": _trunc_normal(keys[0], (self.d_model, E), std_in, jnp.float32),
            "w_gate": _trunc_normal(keys[1], (E, self.d_model, c.d_expert), std_in, self.param_dtype),
            "w_up": _trunc_normal(keys[2], (E, self.d_model, c.d_expert), std_in, self.param_dtype),
            "w_down": _trunc_normal(keys[3], (E, c.d_expert, self.d_model), std_out, self.param_dtype),
        }
        if c.n_shared_experts > 0:
            d_sh = (c.d_shared or c.d_expert) * c.n_shared_experts
            shared = MLP(self.d_model, d_sh, "swiglu", self.param_dtype, self.compute_dtype)
            p["shared"] = shared.init(keys[4])
        return p

    def specs(self):
        s = {
            "router": logical("embed", None),
            "w_gate": logical("expert", "embed", "expert_mlp"),
            "w_up": logical("expert", "embed", "expert_mlp"),
            "w_down": logical("expert", "expert_mlp", "embed"),
        }
        if self.cfg.n_shared_experts > 0:
            c = self.cfg
            d_sh = (c.d_shared or c.d_expert) * c.n_shared_experts
            s["shared"] = MLP(self.d_model, d_sh, "swiglu").specs()
        return s

    def _shared(self):
        c = self.cfg
        d_sh = (c.d_shared or c.d_expert) * c.n_shared_experts
        return MLP(self.d_model, d_sh, "swiglu", self.param_dtype, self.compute_dtype)

    def _dispatch_row(self, params, xf):
        """Per-row dispatch: xf (T, h) -> (y (T, h), stats).

        The sort/cumsum run over the *row-local* token axis, which stays
        unsharded under data parallelism — a global sort over all tokens
        would force an all-gather of the whole batch (measured: 25 TB of
        collectives on deepseek-v2 train_4k; see EXPERIMENTS.md §Perf it.1).
        vmapped over the (sharded) batch dim by ``__call__``.
        """
        c = self.cfg
        cd = self.compute_dtype
        T, h = xf.shape
        E, K = c.n_experts, c.top_k

        logits = jnp.dot(xf.astype(jnp.float32), params["router"],
                         preferred_element_type=jnp.float32)          # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert_ids = jax.lax.top_k(probs, K)                    # (T, K)
        gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

        capacity = int(max(1, -(-T * K * self.capacity_factor // E)))
        flat_e = expert_ids.reshape(-1)                               # (T*K,)
        sort_idx = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[sort_idx]
        group_sizes = jnp.bincount(flat_e, length=E)
        starts = jnp.concatenate([jnp.zeros((1,), group_sizes.dtype),
                                  jnp.cumsum(group_sizes)[:-1]])
        pos = jnp.arange(T * K) - starts[sorted_e]
        keep = pos < capacity
        pos_c = jnp.where(keep, pos, 0).astype(jnp.int32)

        tok_idx = sort_idx // K
        x_sorted = xf[tok_idx] * keep[:, None].astype(cd)
        buf = jnp.zeros((E, capacity, h), cd).at[sorted_e, pos_c].add(
            x_sorted, mode="drop")

        g = jnp.einsum("ech,ehd->ecd", buf, params["w_gate"].astype(cd),
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ech,ehd->ecd", buf, params["w_up"].astype(cd),
                       preferred_element_type=jnp.float32)
        hmid = (jax.nn.silu(g) * u).astype(cd)
        out = jnp.einsum("ecd,edh->ech", hmid, params["w_down"].astype(cd),
                         preferred_element_type=jnp.float32).astype(cd)

        y_sorted = out[sorted_e, pos_c] * keep[:, None].astype(cd)    # (T*K, h)
        y_flat = jnp.zeros((T * K, h), cd).at[sort_idx].set(y_sorted)
        y = (y_flat.reshape(T, K, h) *
             gate.astype(cd).reshape(T, K, 1)).sum(axis=1)

        me = probs.mean(axis=0)                                       # (E,)
        ce = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T * K)
        return y, me, ce

    # ------------------------------------------------------------ EP path
    def _ep_local(self, router_w, w_gate, w_up, w_down, xf, axis: str):
        """Expert-parallel body (inside shard_map over ``axis``).

        Key insight (§Perf cell-1 it.11): the activations are replicated over
        the model axis anyway, so each expert shard just *filters* the tokens
        routed to its local experts — dispatch needs NO communication; the
        only collective is the standard output psum.  GSPMD could not infer
        this from the scatter formulation (it all-reduced dispatch-buffer-
        sized tensors: ~75 GB/layer-pass on deepseek train_4k).
        """
        c = self.cfg
        cd = self.compute_dtype
        N, h = xf.shape
        E, K = c.n_experts, c.top_k
        n_shards = jax.lax.psum(1, axis)
        E_loc = w_gate.shape[0]                               # E / n_shards
        m = jax.lax.axis_index(axis)
        lo = m * E_loc

        logits = jnp.dot(xf.astype(jnp.float32), router_w,
                         preferred_element_type=jnp.float32)  # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert_ids = jax.lax.top_k(probs, K)
        gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

        # keep only copies routed to local experts; rest -> drop bucket E_loc
        flat_e = expert_ids.reshape(-1) - lo                  # (N*K,)
        local = (flat_e >= 0) & (flat_e < E_loc)
        flat_e = jnp.where(local, flat_e, E_loc)
        capacity = int(max(1, -(-N * K * self.capacity_factor // E)))

        sort_idx = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[sort_idx]
        group_sizes = jnp.bincount(flat_e, length=E_loc + 1)
        starts = jnp.concatenate([jnp.zeros((1,), group_sizes.dtype),
                                  jnp.cumsum(group_sizes)[:-1]])
        pos = jnp.arange(N * K) - starts[sorted_e]
        keep = (pos < capacity) & (sorted_e < E_loc)
        pos_c = jnp.where(keep, pos, 0).astype(jnp.int32)
        e_c = jnp.where(keep, sorted_e, 0).astype(jnp.int32)

        tok_idx = sort_idx // K
        x_sorted = xf[tok_idx] * keep[:, None].astype(cd)
        buf = jnp.zeros((E_loc, capacity, h), cd).at[e_c, pos_c].add(
            x_sorted, mode="drop")

        g = jnp.einsum("ech,ehd->ecd", buf, w_gate.astype(cd),
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ech,ehd->ecd", buf, w_up.astype(cd),
                       preferred_element_type=jnp.float32)
        hmid = (jax.nn.silu(g) * u).astype(cd)
        out = jnp.einsum("ecd,edh->ech", hmid, w_down.astype(cd),
                         preferred_element_type=jnp.float32).astype(cd)

        y_sorted = out[e_c, pos_c] * keep[:, None].astype(cd)
        y_flat = jnp.zeros((N * K, h), cd).at[sort_idx].set(y_sorted)
        y = (y_flat.reshape(N, K, h) *
             gate.astype(cd).reshape(N, K, 1)).sum(axis=1)
        y = jax.lax.psum(y, axis)                 # combine expert shards

        me = probs.mean(axis=0)                                # (E,) replicated
        ce = jnp.zeros((E,), jnp.float32).at[
            (expert_ids.reshape(-1))].add(1.0) / (N * K)
        return y, me, ce

    def _ep_call(self, params, x, mesh, dp_axes, axis: str):
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        B, T, h = x.shape
        # divisibility-safe DP: drop axes until their product divides B
        # (long_500k has batch=1 — the whole row set is then replicated)
        dp_axes = tuple(dp_axes or ())
        while dp_axes:
            total = 1
            for a in dp_axes:
                total *= mesh.shape[a]
            if B % total == 0:
                break
            dp_axes = dp_axes[:-1]
        xs_spec = P(dp_axes if dp_axes else None, None, None)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P(axis), P(axis), P(axis), xs_spec),
                 out_specs=(xs_spec, P(), P()), check_vma=False)
        def run(router_w, w_gate, w_up, w_down, xb):
            Bl, Tl, _ = xb.shape
            y, me, ce = self._ep_local(router_w, w_gate, w_up, w_down,
                                       xb.reshape(Bl * Tl, h), axis)
            # me/ce identical on every model shard; average over data shards
            n_dp = 1
            for a in (dp_axes or ()):
                n_dp *= mesh.shape[a]
            if dp_axes:
                me = jax.lax.pmean(me, dp_axes[0] if len(dp_axes) == 1
                                   else dp_axes)
                ce = jax.lax.pmean(ce, dp_axes[0] if len(dp_axes) == 1
                                   else dp_axes)
            return y.reshape(Bl, Tl, h), me, ce

        x = jax.lax.with_sharding_constraint(x, xs_spec)
        return run(params["router"], params["w_gate"], params["w_up"],
                   params["w_down"], x)

    def __call__(self, params, x):
        """x: (B, T, h) -> (y, aux_loss)."""
        c = self.cfg
        B, T, h = x.shape
        state = hints.current()
        mesh = state["mesh"] if state else None
        tp = state["tp"] if state else None
        use_ep = (mesh is not None and tp in mesh.shape
                  and c.n_experts % mesh.shape[tp] == 0)
        if use_ep:
            y, me, ce = self._ep_call(params, x, mesh, state["dp"], tp)
        else:
            y, me, ce = jax.vmap(self._dispatch_row,
                                 in_axes=(None, 0))(params, x)
            me, ce = me.mean(0), ce.mean(0)
        aux = c.n_experts * jnp.sum(me * ce) * c.router_aux_loss
        if c.n_shared_experts > 0:
            y = y + self._shared()(params["shared"], x.astype(self.compute_dtype))
        return y, aux
