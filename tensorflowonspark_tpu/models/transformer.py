"""Decoder-only transformer LM with mesh-parallel attention.

The reference framework predates attention entirely (SURVEY §5.7); this model
is the long-context showcase of the TPU-native design: the same module runs

- ``attention="full"``     — plain causal attention (single device / small S),
- ``attention="flash"``    — the pallas FlashAttention-2 kernels
  (:mod:`tensorflowonspark_tpu.ops.flash_attention`): memory-linear in S,
  hand-scheduled VMEM traffic on TPU, interpret mode elsewhere,
- ``attention="ring"``     — ring attention over the mesh's ``"seq"`` axis
  (sequence parallelism; see :mod:`tensorflowonspark_tpu.parallel.ring`),
- ``attention="ulysses"``  — all-to-all head-parallel attention.

Everything is static-shaped and bf16-friendly; the attention choice only
swaps the core contraction, so checkpoints are interchangeable between modes
(e.g. train with ring on a pod, serve with full on one chip).
"""

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from tensorflowonspark_tpu.models import register_model
from tensorflowonspark_tpu.parallel import ring


class Attention(nn.Module):
    num_heads: int
    head_dim: int
    attention: str = "full"   # full | flash | ring | ulysses
    mesh: Optional[object] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        features = self.num_heads * self.head_dim
        qkv = nn.DenseGeneral((3, self.num_heads, self.head_dim),
                              dtype=self.dtype, name="qkv")(x)
        q, k, v = (qkv[:, :, i] for i in range(3))
        if self.attention == "flash":
            from tensorflowonspark_tpu.ops import flash_attention

            out = flash_attention(q, k, v, causal=True, mesh=self.mesh)
        elif self.attention == "ring":
            assert self.mesh is not None, "ring attention needs a mesh"
            out = ring.ring_attention(q, k, v, self.mesh, causal=True)
        elif self.attention == "ulysses":
            assert self.mesh is not None, "ulysses attention needs a mesh"
            out = ring.ulysses_attention(q, k, v, self.mesh, causal=True)
        else:
            out = ring.reference_attention(q, k, v, causal=True)
        out = out.reshape(out.shape[0], out.shape[1], features)
        return nn.Dense(x.shape[-1], dtype=self.dtype, name="proj")(out)


class _RouterParams(nn.Module):
    """Router weights with ``nn.Dense``'s exact param layout
    (``{kernel, bias}``) but returned raw instead of applied — the
    shard_map EP path routes inside the mapped body
    (:func:`~tensorflowonspark_tpu.parallel.ep.moe_ffn`), so it needs the
    values, while checkpoints must stay interchangeable with the
    ``ep_mode="gspmd"`` layer that applies a real Dense."""

    in_dim: int
    features: int

    @nn.compact
    def __call__(self):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (self.in_dim, self.features))
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        return kernel, bias


class MoEMlp(nn.Module):
    """Switch-style top-1 mixture-of-experts FFN (GShard dispatch/combine).

    Built the TPU way: routing is expressed as dense one-hot einsums (no
    gathers, no dynamic shapes), so the whole layer is three batched
    matmuls on the MXU; capacity-overflowed tokens contribute zero and ride
    the block's residual.  Routing is **grouped per batch row** (the
    GShard/Switch group trick): capacity and the dispatch/combine tensors
    scale with the sequence length, not the global token count, keeping
    dispatch cost linear in batch.

    Expert parallelism: shard the experts' leading dim over the mesh's
    ``expert`` axis —

        tp_param_shardings(params, mesh, axis="expert",
                           rules=[("moe/(w1|w2|b1|b2)", 0), ("", None)])

    (the ``("", None)`` catch-all keeps every non-expert param replicated
    on that axis) — and XLA turns the dispatch/combine einsums into the
    all-to-alls of expert parallelism.

    The load-balance auxiliary (Switch Transformer eq. 4) is sown under
    ``intermediates/moe_aux_loss``; ``loss_fn`` folds it in when present.
    """

    num_experts: int = 8
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    # "gspmd": dense one-hot einsums, XLA partitions them into all-to-alls
    # when params/mesh carry the expert axis (zero model coupling to the
    # mesh).  "shard_map": the explicit DeepSpeed-MoE schedule
    # (parallel/ep.moe_ffn) — identical math (equality-tested), same
    # checkpoint layout, but the collectives are written out; requires
    # ``mesh`` with an ``expert`` axis and the group dim sharded over it.
    ep_mode: str = "gspmd"
    mesh: Optional[object] = None
    # shard_map mode only: mesh axes the caller's batch sharding puts on the
    # group dim (e.g. ("data", "fsdp", "expert")); the EP kernel keeps the
    # batch partitioned over them instead of all-gathering it onto every
    # expert shard.  None = ("expert",) (pure EP).
    ep_batch_axes: Optional[tuple] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        import jax

        batch, seq, d_model = x.shape                            # groups = rows
        hidden = d_model * self.mlp_ratio
        e = self.num_experts
        capacity = max(int(self.capacity_factor * seq / e), 1)

        # router in fp32: tiny matmul, and routing decisions should not
        # flip with the compute dtype
        if self.ep_mode == "shard_map":
            from tensorflowonspark_tpu.parallel import ep as ep_mod

            assert self.mesh is not None, "ep_mode=shard_map needs a mesh"
            # Declare the SAME param tree nn.Dense would (checkpoints stay
            # interchangeable with ep_mode="gspmd"), but hand the raw
            # values to the explicit-EP kernel instead of applying a
            # submodule.
            router = _RouterParams(d_model, e, name="router")
            rk, rb = router()
            w1 = self.param("w1", nn.initializers.lecun_normal(),
                            (e, d_model, hidden))
            b1 = self.param("b1", nn.initializers.zeros, (e, hidden))
            w2 = self.param("w2", nn.initializers.lecun_normal(),
                            (e, hidden, d_model))
            b2 = self.param("b2", nn.initializers.zeros, (e, d_model))
            y, aux = ep_mod.moe_ffn(
                x, {"router": {"kernel": rk, "bias": rb},
                    "w1": w1, "b1": b1, "w2": w2, "b2": b2},
                self.mesh, e, capacity_factor=self.capacity_factor,
                dtype=self.dtype, batch_axes=self.ep_batch_axes)
            self.sow("intermediates", "moe_aux_loss", aux)
            return y

        logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            x.astype(jnp.float32))                               # [G, S, E]
        probs = jax.nn.softmax(logits, axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)                  # [G, S]
        expert_prob = jnp.max(probs, axis=-1)                    # [G, S]
        expert_onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)

        # per-group position of each token in its expert's buffer, in int32
        # (a low-precision cumsum would saturate and collide slots);
        # beyond-capacity tokens are dropped and ride the residual
        pos = jnp.cumsum(expert_onehot, axis=1) * expert_onehot  # [G, S, E]
        pos = pos.sum(axis=-1) - 1                               # [G, S]
        keep = (pos < capacity).astype(x.dtype)
        pos_onehot = jax.nn.one_hot(pos, capacity, dtype=x.dtype)
        dispatch = (expert_onehot.astype(x.dtype)
                    * keep[..., None])[..., None] \
            * pos_onehot[:, :, None, :]                          # [G, S, E, C]

        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (e, d_model, hidden))
        b1 = self.param("b1", nn.initializers.zeros, (e, hidden))
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (e, hidden, d_model))
        b2 = self.param("b2", nn.initializers.zeros, (e, d_model))

        expert_in = jnp.einsum("gsec,gsd->gecd", dispatch, x)    # [G, E, C, D]
        h = jnp.einsum("gecd,edh->gech", expert_in,
                       w1.astype(self.dtype)) + b1.astype(self.dtype)[:, None]
        h = nn.gelu(h)
        out = jnp.einsum("gech,ehd->gecd", h,
                         w2.astype(self.dtype)) + b2.astype(self.dtype)[:, None]
        combine = dispatch * expert_prob.astype(x.dtype)[..., None, None]
        mixed = jnp.einsum("gsec,gecd->gsd", combine, out)       # [G, S, D]

        # Switch load-balance loss: E * sum_e fraction_e * mean_prob_e
        fraction = expert_onehot.astype(jnp.float32).mean(axis=(0, 1))
        mean_prob = probs.mean(axis=(0, 1))
        self.sow("intermediates", "moe_aux_loss",
                 e * jnp.sum(fraction * mean_prob))
        return mixed


class Block(nn.Module):
    num_heads: int
    head_dim: int
    mlp_ratio: int = 4
    attention: str = "full"
    mlp: str = "dense"        # dense | moe
    num_experts: int = 8
    capacity_factor: float = 1.25
    ep_mode: str = "gspmd"    # gspmd | shard_map (see MoEMlp)
    mesh: Optional[object] = None
    ep_batch_axes: Optional[tuple] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.LayerNorm(dtype=self.dtype)(x)
        x = x + Attention(self.num_heads, self.head_dim, self.attention,
                          self.mesh, self.dtype)(h)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        if self.mlp == "moe":
            h = MoEMlp(num_experts=self.num_experts,
                       mlp_ratio=self.mlp_ratio,
                       capacity_factor=self.capacity_factor,
                       ep_mode=self.ep_mode, mesh=self.mesh,
                       ep_batch_axes=self.ep_batch_axes,
                       dtype=self.dtype, name="moe")(h)
        else:
            h = nn.Dense(x.shape[-1] * self.mlp_ratio, dtype=self.dtype)(h)
            h = nn.gelu(h)
            h = nn.Dense(x.shape[-1], dtype=self.dtype)(h)
        return x + h


class TransformerLM(nn.Module):
    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    head_dim: int = 64
    max_seq_len: int = 2048
    attention: str = "full"
    mlp: str = "dense"        # dense | moe
    num_experts: int = 8
    capacity_factor: float = 1.25
    ep_mode: str = "gspmd"    # gspmd | shard_map (see MoEMlp)
    mesh: Optional[object] = None
    ep_batch_axes: Optional[tuple] = None
    remat: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        d_model = self.num_heads * self.head_dim
        x = nn.Embed(self.vocab_size, d_model, dtype=self.dtype,
                     name="embed")(tokens)
        pos = nn.Embed(self.max_seq_len, d_model, dtype=self.dtype,
                       name="pos_embed")(jnp.arange(tokens.shape[1]))
        x = x + pos[None]
        # remat trades FLOPs for HBM: each block's activations (incl. the
        # full-attention S x S probs the backward pass would otherwise
        # keep per layer) are recomputed during backprop instead of
        # stored — the standard TPU recipe for configs whose stored
        # activations exceed HBM (e.g. d2048 x 16L x b16 full attention).
        block_cls = nn.remat(Block) if self.remat else Block
        for i in range(self.num_layers):
            x = block_cls(self.num_heads, self.head_dim,
                          attention=self.attention, mlp=self.mlp,
                          num_experts=self.num_experts,
                          capacity_factor=self.capacity_factor,
                          ep_mode=self.ep_mode, mesh=self.mesh,
                          ep_batch_axes=self.ep_batch_axes,
                          dtype=self.dtype, name="block_%d" % i)(x)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        # weight-tied readout keeps the big vocab matmul on the MXU once
        embed = self.variables["params"]["embed"]["embedding"]
        return (x @ embed.T.astype(self.dtype)).astype(jnp.float32)


@register_model("transformer_lm")
def build_transformer(vocab_size=32000, num_layers=4, num_heads=8,
                      head_dim=64, max_seq_len=2048, attention="full",
                      mlp="dense", num_experts=8, capacity_factor=1.25,
                      ep_mode="gspmd", mesh=None, ep_batch_axes=None,
                      remat=False, dtype="float32"):
    return TransformerLM(vocab_size=vocab_size, num_layers=num_layers,
                         num_heads=num_heads, head_dim=head_dim,
                         max_seq_len=max_seq_len, attention=attention,
                         mlp=mlp, num_experts=num_experts,
                         capacity_factor=capacity_factor, ep_mode=ep_mode,
                         mesh=mesh, ep_batch_axes=ep_batch_axes,
                         remat=remat, dtype=jnp.dtype(dtype))


def _sum_moe_aux(tree):
    """Sum every ``moe_aux_loss`` sown anywhere in the intermediates tree;
    None when the model has no MoE layers."""
    total, found = 0.0, False
    if isinstance(tree, dict):
        for key, val in tree.items():
            if key == "moe_aux_loss":
                for v in (val if isinstance(val, (tuple, list)) else (val,)):
                    total = total + v
                    found = True
            else:
                sub = _sum_moe_aux(val)
                if sub is not None:
                    total = total + sub
                    found = True
    return total if found else None


def loss_fn(model, moe_aux_weight=0.01):
    """Next-token cross-entropy with per-row masking.

    The model is applied to the *full* sequence (not ``tokens[:, :-1]``) so
    the sequence length stays divisible by the mesh's ``seq`` axis for
    ring/ulysses attention; the last position, which has no target, is
    excluded via a position mask instead.

    MoE models' sown load-balance auxiliaries are folded in with weight
    ``moe_aux_weight`` (Switch Transformer's alpha=0.01 default) and
    reported via ``aux["moe_aux_loss"]``.
    """
    import optax

    def loss(params, batch, mask):
        tokens = batch["tokens"].astype(jnp.int32)
        logits, state = model.apply({"params": params}, tokens,
                                    mutable=["intermediates"])   # [B, S, V]
        targets = jnp.roll(tokens, -1, axis=1)                # last pos junk
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        pos_mask = jnp.ones(tokens.shape[1]).at[-1].set(0.0)  # drop last pos
        ce = (ce * pos_mask[None]).sum(axis=-1) / pos_mask.sum()
        ce = (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        aux = {}
        lb = _sum_moe_aux(dict(state.get("intermediates", {})))
        if lb is not None:
            aux["moe_aux_loss"] = lb
            ce = ce + moe_aux_weight * lb
        return ce, aux

    return loss
