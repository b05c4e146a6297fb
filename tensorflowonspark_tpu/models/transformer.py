"""Decoder-only transformer LM with mesh-parallel attention.

The reference framework predates attention entirely (SURVEY §5.7); this model
is the long-context showcase of the TPU-native design: the same module runs

- ``attention="full"``     — exact attention on one device (the default):
  the flash kernels where the process holds a TPU and the row tiles (by
  512, 256 or 128: ``ops.flash_attention.full_attention_block``, which
  chooses the block from the row; on a mesh, where the kernels' per-shard
  mapping fits), the plain contraction elsewhere (every CPU process, a row
  of 64 or 1,000 tokens, a sequence-parallel mesh).  The same mathematics
  either way; which one a layer took is said by the presence of the
  ``flash_*`` keys in ``aux["counters"]``,
- ``attention="flash"``    — the pallas FlashAttention-2 kernels
  (:mod:`tensorflowonspark_tpu.ops.flash_attention`) always, at the
  layer's ``flash_block``: memory-linear in S, hand-scheduled VMEM traffic
  on TPU, interpret mode elsewhere, a ``ValueError`` that names the shapes
  where the row does not tile,
- ``attention="ring"``     — ring attention over the mesh's ``"seq"`` axis
  (sequence parallelism; see :mod:`tensorflowonspark_tpu.parallel.ring`),
- ``attention="ulysses"``  — all-to-all head-parallel attention.

Everything is static-shaped and bf16-friendly; the attention choice only
swaps the core contraction, so checkpoints are interchangeable between modes
(e.g. train with ring on a pod, serve with full on one chip).

**One decoder, a pattern of layers.**  ``TransformerLM`` is built from a
:class:`DecoderSpec`, its only form: the vocabulary, the position table, the
final norm and one :class:`LayerSpec` a layer (norm kind and epsilon,
operator kind, feed-forward kind, position kind, and their widths).  Beside
the description it takes the run-time choices alone (``attention``,
``ep_mode``, ``mesh``, ``ep_batch_axes``, ``remat``, ``dtype``).  Who writes
a description:

- :func:`gpt2_spec`, for :func:`build_transformer` (registered as
  ``transformer_lm``): LayerNorm, learned positions, fused-qkv multi-head
  attention and a GELU MLP (or the top-1 Switch ``MoEMlp``) in every layer;
  its parameter tree (``block_i/LayerNorm_0``, ``Attention_0/qkv``,
  ``Dense_0``, ``Dense_1``, ``pos_embed``, ``embed``) and arithmetic are the
  GPT-2 decoder's as they always were;
- a family's spec function, one module a family under
  :mod:`tensorflowonspark_tpu.models.families` (``lfm2_moe``,
  ``deepseek_v2``, ``keye_vl2``, ``mellum2``, ``nemotron_h``,
  ``olmo_hybrid``), which reads
  the family's ``config.json`` and which :func:`register_decoder` registers
  with ``get_model``.  A new family is a file there; a layer kind it brings
  is a ``LayerSpec`` value and a branch of ``Block._op`` / ``Block._ff``
  here, its kernels a module under ``ops/``.

**What a layer counts** it sows as one dict of device scalars,
``self.sow("intermediates", "counters", {...})``, under the names
``train.Trainer.counters_snapshot()`` publishes; :func:`loss_fn` adds the
dicts up key by key into ``aux["counters"]`` and the ``Trainer`` adds that up
over the steps.  Neither knows a key.

Scopes a device trace can be read by (``jax.named_scope`` under the flax
module names): ``block_i/short_conv``, ``block_i/attention/flash`` (a layer
whose queries read every causal key, or the keys an index picks),
``block_i/attention/flash_window`` (a layer with a window: the banded
kernels, so that a reader of ``attention/flash`` does not take them in),
``block_i/attention/latent`` (everything latent attention puts round the
kernel: the three projections, the latent's norm, RoPE, building K),
``block_i/attention/indexer`` (the index's three projections, its norm and
RoPE), ``block_i/attention/select`` (the one kernel that scores every causal
pair and picks each query's keys: the scores never leave VMEM, so they have
no scope apart from the search), ``block_i/attention/tiles`` (the count of
the tiles the picks touch), ``block_i/attention/index_loss`` (the kernel of
the index's loss, in a training step the form with its gradients),
``block_i/moe/route`` (router, top-k, sort), ``moe/dispatch`` (gather),
``moe/experts`` (the grouped products), ``moe/combine`` (scale, gather back),
``moe/shared`` (the shared expert); of a Mamba-2 layer
``block_i/mamba/in_proj``, ``mamba/conv`` (taps, bias, silu), ``mamba/scan``
(the scan's kernels and the decays' sums they read, nothing else),
``mamba/gate_norm`` (the skip, the gate, the grouped norm) and
``mamba/out_proj``; of a Gated DeltaNet layer ``block_i/delta/in_proj``,
``delta/conv`` (taps, silu), ``delta/scan`` (the L2 norms of q and k, the
delta rule's kernels and the decays' sums they read, nothing else),
``delta/gate_norm`` (a head's norm, the gate) and ``delta/out_proj``; its
counters are ``delta_chunks``, ``delta_state_bytes`` and ``delta_layers``.
"""

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models import register_model
from tensorflowonspark_tpu.parallel import ring


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One decoder layer, ``x + op(norm(x))`` then ``x + ff(norm(x))`` (or,
    ``norm_place="output"``, ``x + norm(op(x))`` then ``x + norm(ff(x))``):
    its kinds, and the widths they need.  A layer may be one half alone
    (``op="none"`` or ``ff="none"``): one norm and one residual add."""

    op: str = "attention"          # attention | conv (gated short convolution)
    #                                | mla (latent attention, see below)
    #                                | mamba2 (state-space scan: Mamba2)
    #                                | gated_delta (delta rule: GatedDelta)
    #                                | none (a feed-forward layer)
    ff: str = "gelu"               # gelu | switch (top-1, capacity: MoEMlp)
    #                                | swiglu | experts (top-k: TopKExperts)
    #                                | relu2 (two matrices, relu(.)**2)
    #                                | none (a mixer layer)
    norm: str = "layernorm"        # layernorm | rmsnorm
    norm_eps: float = 1e-6
    norm_place: str = "input"      # input: a part reads the normed stream
    #                                | output: the part's result is normed
    #                                before it is added to the stream
    positions: str = "learned"     # learned (a table added to the embedding:
    #                                nothing in the layer) | rope (on q and k)
    #                                | none (nothing anywhere)
    num_heads: int = 8
    head_dim: int = 64
    # None: fused qkv projection with biases, as many KV heads as query heads
    # (GPT-2's); a number: separate q/k/v/o projections without biases,
    # query head i reading KV head i // (num_heads // num_kv_heads)
    num_kv_heads: Optional[int] = None
    # RMSNorm on q and on k: False | "head" (or True: over a head's width,
    # one weight [head_dim] for all heads) | "whole" (over the whole
    # projection before the split into heads, a weight [heads * head_dim])
    qk_norm: object = False
    rope_theta: float = 10000.0
    rope_pairing: str = "half"     # half: dimension i turns with i + D/2
    #                                | interleaved: 2i with 2i + 1
    # YaRN: (factor, original_max_position_embeddings, beta_fast, beta_slow,
    # mscale, mscale_all_dim); None: the plain frequencies of rope_theta
    rope_yarn: Optional[Tuple[float, ...]] = None
    # op="mla": q is num_heads x (nope_dim + rope_dim) straight from x; k's
    # nope_dim and v's v_dim a head come up from an RMSNormed latent of
    # kv_rank, k's rope_dim is one rotary key shared by all heads; RoPE on
    # the rope_dim parts only; scores times attn_scale (None: 1/sqrt(width))
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    attn_scale: Optional[float] = None
    # a learned index picks each query's keys (attention="flash"):
    # index_heads index query heads of index_dim and one index key head
    # (LayerNorm, RoPE at rope_theta over all of index_dim) score every
    # causal pair on a detached input, sum_j w_j relu(q_j . k), and attention
    # runs over the index_topk keys of largest score; 0 = every causal key,
    # and then nothing of a layer changes
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # q and k block of attention="flash" ("full" reads its block from the
    # row where it takes the kernels: ops.flash_attention.row_block)
    flash_block: int = 128
    # a query reads its last ``window`` keys, its own among them (t - s <
    # window); 0 = every causal key, and then nothing of a layer changes.
    # One that covers the row is every causal key too
    window: int = 0
    conv_kernel: int = 3
    ff_size: int = 0               # the dense feed-forward's width
    num_experts: int = 8           # the router's outputs
    experts_per_token: int = 1
    expert_size: int = 0           # one expert's width (ff="experts")
    # (first, count): the contiguous range of the router's experts whose
    # weights this layer holds (one chip's share under expert parallelism);
    # None holds them all
    held_experts: Optional[Tuple[int, int]] = None
    router_score: str = "sigmoid"  # sigmoid | softmax (over all the experts)
    selection_bias: bool = True    # an expert_bias leaf in the top-k's choice
    norm_topk: bool = True
    routed_scaling: float = 1.0
    shared_size: int = 0           # a shared expert's width beside the
    #                                routed experts; 0 = none
    # an expert's (and the shared expert's) form: swiglu, three matrices,
    # (silu(x W_1) * (x W_3)) W_2 | relu2, two, relu(x W_1)**2 W_2
    expert_act: str = "swiglu"
    capacity_factor: float = 1.25  # ff="switch"
    # op="mamba2": ssm_heads heads of ssm_head_dim, a state of ssm_state a
    # head, ssm_groups groups of B and C, conv_kernel taps, chunks of
    # ssm_chunk positions
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
    # op="gated_delta": delta_heads heads with keys (and queries) of
    # delta_key_dim and values of delta_value_dim, conv_kernel taps, chunks
    # of delta_chunk positions; delta_neg_eigval: the write strength b in
    # (0, 2) for (0, 1), so that I - b k k^T may turn a key's part around
    delta_heads: int = 0
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_neg_eigval: bool = False
    delta_chunk: int = 64

    def __post_init__(self):
        if self.op == "none" and self.ff == "none":
            raise ValueError("a layer with neither op nor ff")
        if self.norm_place not in ("input", "output"):
            raise ValueError("norm_place={!r}".format(self.norm_place))
        if self.qk_norm not in (False, True, "head", "whole"):
            raise ValueError("qk_norm={!r}".format(self.qk_norm))
        if self.window < 0 or self.window and (
                self.op in ("conv", "mamba2", "gated_delta", "none")
                or self.index_topk > 0):
            raise ValueError(
                "window={} wants an attention layer without an index over "
                "the keys (op={!r}, index_topk={})".format(
                    self.window, self.op, self.index_topk))


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    """The whole decoder: embedding, optional position table, the layers,
    the final norm, and the read-out: the embedding's transpose, or a
    ``head`` matrix of its own."""

    vocab_size: int
    hidden_size: int
    layers: Tuple[LayerSpec, ...]
    learned_positions: int = 0     # rows of the position table; 0 = none
    norm: str = "layernorm"        # the final norm
    norm_eps: float = 1e-6
    tied_readout: bool = True      # False: a ``head`` leaf [hidden, vocab]


def gpt2_layer(num_heads, head_dim, mlp="dense", mlp_ratio=4, num_experts=8,
               capacity_factor=1.25):
    """One layer of the GPT-2 decoder: LayerNorm, fused-qkv multi-head
    attention under learned positions, a GELU MLP (``mlp="moe"``: the top-1
    Switch layer)."""
    return LayerSpec(
        op="attention", ff="switch" if mlp == "moe" else "gelu",
        norm="layernorm", norm_eps=1e-6, positions="learned",
        num_heads=num_heads, head_dim=head_dim,
        ff_size=num_heads * head_dim * mlp_ratio, num_experts=num_experts,
        capacity_factor=capacity_factor)


def gpt2_spec(vocab_size, num_layers, num_heads, head_dim, max_seq_len,
              **layer):
    """The GPT-2 decoder of :func:`build_transformer`'s sizes (``layer``:
    :func:`gpt2_layer`'s options)."""
    return DecoderSpec(
        vocab_size=vocab_size, hidden_size=num_heads * head_dim,
        layers=(gpt2_layer(num_heads, head_dim, **layer),) * num_layers,
        learned_positions=max_seq_len)


def _norm(kind, eps, dtype):
    if kind == "rmsnorm":
        return nn.RMSNorm(epsilon=eps, dtype=dtype)
    return nn.LayerNorm(epsilon=eps, dtype=dtype)


def yarn_mscale(scale, mscale):
    """YaRN's attention factor ``0.1 * mscale * ln(scale) + 1`` (1 where the
    context is not stretched)."""
    import math

    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_frequencies(dim, theta, yarn=None):
    """``(inv [dim / 2] float32, factor)``: the angle a position of pair
    ``i``, and what cos and sin are multiplied by.  Plain: ``theta ** (-2i /
    dim)`` and 1.  ``yarn = (factor, original_max, beta_fast, beta_slow,
    mscale, mscale_all_dim)``: pairs that turn more than ``beta_fast`` times
    over the original context keep the plain frequency, those that turn less
    than ``beta_slow`` times take it divided by ``factor``, a linear ramp
    over the pair index between the two; cos and sin times the ratio of
    :func:`yarn_mscale` of ``mscale`` and of ``mscale_all_dim``."""
    import math

    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if yarn is None:
        return inv, 1.0
    factor, original, beta_fast, beta_slow, mscale, mscale_all = yarn

    def pair_turning(times):    # the pair index that turns so often
        return dim * math.log(original / (times * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return (inv / factor * ramp + inv * (1.0 - ramp),
            yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all))


def rope(x, inv, pairing="half", factor=1.0):
    """Rotary positions 0..S-1 on ``x [B, S, H, D]`` at the frequencies
    ``inv [D / 2]`` (:func:`rope_frequencies`), angles in float32.
    ``pairing="half"``: dimension i turns with i + D/2.  ``"interleaved"``:
    dimension 2i turns with 2i + 1, and the turned pairs come out in the
    half layout (all first members, then all second: the DeepSeek family's
    code does the same; q and k are permuted alike, so scores are those of
    pairs turned in place)."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    if pairing == "interleaved":
        x1, x2 = x[..., 0::2], x[..., 1::2]
    else:
        x1, x2 = x[..., :half], x[..., half:]
    x1, x2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


@jax.custom_vjp
def _backward_together(main, side):
    """``(main, side)`` as they come; in the backward pass ``main``'s
    gradient is not handed on before ``side``'s is made.

    For a branch whose gradient ends in parameters alone (the index of
    :meth:`Attention._indexed`, which reads a detached ``x``): nothing of the
    layers below waits for it, so the compiler is free to put its backward
    pass behind theirs, and to hold what that reads all the while (the
    gradients the index's loss kept in the forward pass, 159 MB a layer at
    32,768-token rows) over their expert layers, the step's peak
    (``tests/test_chip_compile_keye_vl2.py``)."""
    return main, side


_backward_together.defvjp(
    lambda main, side: ((main, side), None),
    lambda _, grads: jax.lax.optimization_barrier(grads))


class Attention(nn.Module):
    num_heads: int
    head_dim: int
    attention: str = "full"   # full | flash | ring | ulysses
    mesh: Optional[object] = None
    dtype: jnp.dtype = jnp.float32
    # grouped-query form (see LayerSpec.num_kv_heads); None = GPT-2's
    num_kv_heads: Optional[int] = None
    qk_norm: object = False   # LayerSpec.qk_norm
    norm_eps: float = 1e-6
    rope_theta: Optional[float] = None
    rope_yarn: Optional[Tuple[float, ...]] = None   # LayerSpec.rope_yarn
    flash_block: int = 128
    window: int = 0           # LayerSpec.window; 0 = every causal key
    # the latent form (LayerSpec op="mla"): the layer's description, whose
    # kv_rank, nope_dim, rope_dim, v_dim, rope_* and attn_scale are read
    latent: Optional[LayerSpec] = None
    # a learned index over the keys (LayerSpec.index_*); 0 heads = none
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0

    @nn.nowrap     # no scope of its own: the caller's named scopes are read
    def _index(self, x):
        """The index's queries ``[B, S, J, E]``, its one key a position
        ``[B, S, E]`` and its head weights ``[B, S, J]`` (float32, with the
        scores' ``J ** -0.5 * E ** -0.5``), all of a detached ``x``:
        parameters ``index_q``, ``index_k``, ``index_k_norm``, ``index_w``."""
        x = jax.lax.stop_gradient(x)
        heads, dim = self.index_heads, self.index_dim
        iq = nn.DenseGeneral((heads, dim), use_bias=False, dtype=self.dtype,
                             name="index_q")(x)
        ik = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype,
                          name="index_k_norm")(
            nn.Dense(dim, use_bias=False, dtype=self.dtype,
                     name="index_k")(x))
        inv, _ = rope_frequencies(dim, self.rope_theta)
        iq, ik = rope(iq, inv), rope(ik[:, :, None], inv)[:, :, 0]
        iw = nn.Dense(heads, use_bias=False, dtype=jnp.float32,
                      name="index_w")(x.astype(jnp.float32))
        return iq, ik, iw * (heads ** -0.5 * dim ** -0.5)

    def _latent_qkv(self, x):
        """q ``[B, S, H, nope + rope]``, k alike, v ``[B, S, H, v_dim]`` of
        latent attention: parameters ``q``, ``kv_a``, ``kv_norm``, ``kv_b``."""
        spec = self.latent
        heads, nope = self.num_heads, spec.nope_dim
        q = nn.DenseGeneral((heads, nope + spec.rope_dim), use_bias=False,
                            dtype=self.dtype, name="q")(x)
        latent, k_pe = jnp.split(
            nn.Dense(spec.kv_rank + spec.rope_dim, use_bias=False,
                     dtype=self.dtype, name="kv_a")(x),
            [spec.kv_rank], axis=-1)
        kv = nn.DenseGeneral(
            (heads, nope + spec.v_dim), use_bias=False, dtype=self.dtype,
            name="kv_b")(nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                                    name="kv_norm")(latent))
        inv, factor = rope_frequencies(spec.rope_dim, spec.rope_theta,
                                       spec.rope_yarn)
        q_pe = rope(q[..., nope:], inv, spec.rope_pairing, factor)
        k_pe = rope(k_pe[:, :, None], inv, spec.rope_pairing, factor)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        # the one rotary key a position, for every head
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe, k_pe.shape[:2] + (heads, spec.rope_dim))],
            axis=-1)
        return q, k, kv[..., nope:]

    @nn.nowrap
    def _sow_grid(self, x, block, window=None):
        """Sow what the flash kernels' forward grid takes for this call's
        rows and heads at blocks of ``block``: its steps
        (``flash_grid_steps``), and the tiles among them that compute
        (``flash_tiles_computed``; ``ops.flash_attention.grid_tiles``, known
        when the step is traced)."""
        from tensorflowonspark_tpu.ops.flash_attention import grid_tiles

        steps, computed = grid_tiles(x.shape[1], block, block, window=window)
        heads = x.shape[0] * self.num_heads
        self.sow("intermediates", "counters", {
            "flash_grid_steps": jnp.asarray(heads * steps, jnp.int32),
            "flash_tiles_computed": jnp.asarray(heads * computed, jnp.int32)})

    @nn.nowrap
    def _indexed(self, x, q, k, v):
        """Attention over the keys the index picks; sows the index's loss
        (``dsa_index_loss [B]``) and, as counters, the causal ``[block,
        block]`` tiles of (queries, keys) and those of them that hold a
        picked key (``dsa_tiles_causal``, ``dsa_tiles_touched``)."""
        from tensorflowonspark_tpu.ops import (flash_attention_lse,
                                               sparse_index)

        if self.attention != "flash" or self.latent is not None:
            raise ValueError(
                "an index over the keys runs with grouped-query attention "
                "under attention=\"flash\": {!r}".format(self.attention))
        block = self.flash_block
        with jax.named_scope("indexer"):
            iq, ik, iw = self._index(x)
        # the index's backward runs in this layer's backward pass
        q, (iq, ik, iw) = _backward_together(q, (iq, ik, iw))
        with jax.named_scope("select"):
            bits, index_lse = sparse_index.select_keys(
                iq, ik, iw, self.index_topk, chunk=block)
        with jax.named_scope("tiles"):
            touched, causal = sparse_index.tiles_touched(
                bits, x.shape[1], block)
        with jax.named_scope("flash"):
            out, lse = flash_attention_lse(
                q, k, v, causal=True, block_q=block, block_k=block,
                key_bits=bits)
        self._sow_grid(x, block)
        with jax.named_scope("index_loss"):
            loss = sparse_index.index_loss(iq, ik, iw, q, k, lse, index_lse,
                                           bits, block=block)
        self.sow("intermediates", "dsa_index_loss", loss)
        self.sow("intermediates", "counters", {
            "dsa_tiles_touched": touched,
            "dsa_tiles_causal": jnp.asarray(causal, jnp.int32),
            "dsa_layers_steps": jnp.asarray(1, jnp.int32)})
        return out

    @nn.compact
    def __call__(self, x):
        features = self.num_heads * self.head_dim
        scale = None
        if self.latent is not None:
            with jax.named_scope("latent"):
                q, k, v = self._latent_qkv(x)
            features = self.num_heads * self.latent.v_dim
            scale = self.latent.attn_scale
        elif self.num_kv_heads is None:
            qkv = nn.DenseGeneral((3, self.num_heads, self.head_dim),
                                  dtype=self.dtype, name="qkv")(x)
            q, k, v = (qkv[:, :, i] for i in range(3))
        else:
            q, k, v = (
                nn.DenseGeneral((heads, self.head_dim), use_bias=False,
                                dtype=self.dtype, name=name)(x)
                for name, heads in (("q", self.num_heads),
                                    ("k", self.num_kv_heads),
                                    ("v", self.num_kv_heads)))
        if self.qk_norm == "whole":     # the statistic over all the heads
            q, k = (
                nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name=name)(
                    t.reshape(t.shape[:2] + (-1,))).reshape(t.shape)
                for name, t in (("q_norm", q), ("k_norm", k)))
        elif self.qk_norm:
            q = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                           name="q_norm")(q)
            k = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                           name="k_norm")(k)
        if self.rope_theta is not None and self.latent is None:
            inv, factor = rope_frequencies(self.head_dim, self.rope_theta,
                                           self.rope_yarn)
            q, k = rope(q, inv, factor=factor), rope(k, inv, factor=factor)
        window = self.window or None
        # the kernels' block: under "flash" the one the layer states; under
        # "full" the one the row gives, where the kernels serve (None: the
        # plain contraction)
        block = None
        if self.attention == "flash":
            block = self.flash_block
        elif self.attention == "full":
            from tensorflowonspark_tpu.ops.flash_attention import (
                full_attention_block)

            block = full_attention_block(q, k, v, self.mesh)
        if self.index_heads:
            out = self._indexed(x, q, k, v)
        elif block:
            from tensorflowonspark_tpu.ops import flash_attention
            from tensorflowonspark_tpu.ops.flash_attention import band_tiles

            with jax.named_scope("flash_window" if window else "flash"):
                out = flash_attention(q, k, v, causal=True, mesh=self.mesh,
                                      block_q=block, block_k=block,
                                      scale=scale, window=window)
            self._sow_grid(x, block, window)
            if window:      # what the band leaves of the causal tiles
                computed, causal = band_tiles(x.shape[1], block, window)
                self.sow("intermediates", "counters", {
                    "swa_tiles_computed": jnp.asarray(x.shape[0] * computed,
                                                      jnp.int32),
                    "swa_tiles_causal": jnp.asarray(x.shape[0] * causal,
                                                    jnp.int32),
                    "swa_layers_steps": jnp.asarray(1, jnp.int32)})
        else:
            group = self.num_heads // k.shape[2]
            if group > 1:   # the contractions below want a KV head each
                k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
            if self.attention == "ring":
                assert self.mesh is not None, "ring attention needs a mesh"
                out = ring.ring_attention(q, k, v, self.mesh, causal=True,
                                          scale=scale, window=window)
            elif self.attention == "ulysses":
                assert self.mesh is not None, "ulysses attention needs a mesh"
                out = ring.ulysses_attention(q, k, v, self.mesh, causal=True,
                                             scale=scale, window=window)
            else:
                out = ring.reference_attention(q, k, v, causal=True,
                                               scale=scale, window=window)
        out = out.reshape(out.shape[0], out.shape[1], features)
        fused = self.num_kv_heads is None and self.latent is None
        return nn.Dense(x.shape[-1], use_bias=fused, dtype=self.dtype,
                        name="proj")(out)


def _causal_taps(z, taps):
    """``c_t = sum_j taps[j] * z_{t-j}`` a channel of ``z [B, S, C]``
    (depthwise, causal, zeros before the sequence), ``taps [kernel, C]``."""
    kernel, seq = taps.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (kernel - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, kernel - 1 - j:kernel - 1 - j + seq]
               for j in range(kernel))


class ShortConv(nn.Module):
    """Gated short convolution (the LFM2 family's token mixer): ``[B, C, u]
    = split3(x W_in)``, ``z = B * u``, ``c_t = sum_j w_j * z_{t-j}``
    (depthwise, causal, ``kernel`` taps a channel, zeros before the
    sequence), ``y = (C * c) W_out``; no biases."""

    kernel: int = 3
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        gate_b, gate_c, u = jnp.split(
            nn.Dense(3 * d, use_bias=False, dtype=self.dtype,
                     name="in_proj")(x), 3, axis=-1)
        taps = self.param("conv", nn.initializers.normal(0.02),
                          (self.kernel, d)).astype(self.dtype)
        return nn.Dense(d, use_bias=False, dtype=self.dtype,
                        name="out_proj")(
                            gate_c * _causal_taps(gate_b * u, taps))


def _inverse_softplus_steps(low=0.001, high=0.1, floor=1e-4):
    """Initialiser of ``dt_bias``: the inverse softplus of a step size drawn
    log-uniformly in ``[low, high]`` and floored (Mamba-2's)."""
    import math

    def init(key, shape, dtype=jnp.float32):
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(low), math.log(high))), floor)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)

    return init


class Mamba2(nn.Module):
    """A Mamba-2 mixer (state-space duality, arXiv:2405.21060): ``[z | xBC |
    dt] = u W_in`` (``heads * head_dim | heads * head_dim + 2 groups state |
    heads``, no bias); ``xBC = silu(conv(xBC) + b)``, the convolution
    depthwise and causal, ``conv_kernel`` taps a channel (``c_t = sum_j w_j
    z_{t-j}``, zeros before the row), over x, B and C together; ``dt =
    softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; the scan ``S_t =
    exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``
    (:func:`~tensorflowonspark_tpu.ops.ssd_scan.ssd_scan`, chunks of
    ``chunk``; head ``h`` reads group ``h // (heads / groups)``); ``y =
    RMSNorm_group(y * silu(z)) * w`` over groups of ``heads * head_dim /
    groups``; ``y W_out``.  Products in ``dtype``; the step sizes, ``A``,
    the decays, the carried state and the norm's statistics float32;
    ``A_log``, ``dt_bias`` and ``D`` float32 leaves.

    The chunks scanned and the bytes of the chunk states written are sown
    as counters (``ssd_chunks``; ``ssd_state_bytes``, float32: a step's can
    pass 2**31; ``ssd_layers`` the layer calls)."""

    heads: int
    head_dim: int
    state: int
    groups: int = 1
    conv_kernel: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        from tensorflowonspark_tpu.ops import ssd_scan as ssd

        batch, seq, d_model = u.shape
        f32 = jnp.float32
        inner, bc = self.heads * self.head_dim, self.groups * self.state
        z, xbc, dt = jnp.split(
            nn.Dense(2 * inner + 2 * bc + self.heads, use_bias=False,
                     dtype=self.dtype, name="in_proj")(u),
            [inner, 2 * inner + 2 * bc], axis=-1)
        taps = self.param("conv", nn.initializers.normal(0.02),
                          (self.conv_kernel, inner + 2 * bc))
        conv_bias = self.param("conv_bias", nn.initializers.zeros,
                               (inner + 2 * bc,))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, f32, 1.0, 16.0)), (self.heads,))
        dt_bias = self.param("dt_bias", _inverse_softplus_steps(),
                             (self.heads,))
        skip = self.param("D", nn.initializers.ones, (self.heads,))
        scale = self.param("norm", nn.initializers.ones, (inner,))
        with jax.named_scope("conv"):
            xbc = nn.silu(conv_bias.astype(self.dtype)
                          + _causal_taps(xbc, taps.astype(self.dtype)))
        x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
        x = x.reshape(batch, seq, self.heads, self.head_dim)
        b = b.reshape(batch, seq, self.groups, self.state)
        c = c.reshape(batch, seq, self.groups, self.state)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        with jax.named_scope("scan"):
            y = ssd.ssd_scan(x, dt, dt * -jnp.exp(a_log.astype(f32)), b, c,
                             chunk=self.chunk)
        chunks, state_bytes = ssd.chunk_counts(x, b, self.chunk)
        self.sow("intermediates", "counters", {
            "ssd_chunks": jnp.asarray(chunks, jnp.int32),
            "ssd_state_bytes": jnp.asarray(state_bytes, f32),
            "ssd_layers": jnp.asarray(1, jnp.int32)})
        with jax.named_scope("gate_norm"):
            y = y.astype(f32) + skip.astype(f32)[:, None] * x.astype(f32)
            y = y.reshape(batch, seq, inner) * nn.silu(z.astype(f32))
            by_group = y.reshape(batch, seq, self.groups, inner // self.groups)
            y = (by_group * jax.lax.rsqrt(
                jnp.square(by_group).mean(-1, keepdims=True) + self.norm_eps)
                 ).reshape(batch, seq, inner) * scale.astype(f32)
        return nn.Dense(d_model, use_bias=False, dtype=self.dtype,
                        name="out_proj")(y.astype(self.dtype))


class GatedDelta(nn.Module):
    """A Gated DeltaNet mixer (arXiv:2412.06464): ``[q | k | v | z | a | b]
    = x W_in`` (``heads * key_dim`` twice, ``heads * value_dim`` twice,
    ``heads`` twice; no bias); ``q, k, v = silu(conv(.))``, the convolution
    depthwise and causal, ``conv_kernel`` taps a channel, no bias, zeros
    before the row; a head's ``q`` and ``k`` L2-normalised (``x / sqrt(sum
    x^2 + 1e-6)``), ``q`` times ``key_dim ** -0.5``; ``b = sigmoid(b)``
    (times 2 where ``neg_eigval``), ``g = -exp(A_log) softplus(a +
    dt_bias)`` a head; the delta rule ``S_t = exp(g_t) S_{t-1} + b_t k_t^T
    (v_t - exp(g_t) k_t S_{t-1})``, ``o_t = q_t S_t``
    (:func:`~tensorflowonspark_tpu.ops.gated_delta.gated_delta_rule`, chunks
    of ``chunk``); ``y = RMSNorm_head(o) * w * silu(z)``, one weight
    ``[value_dim]`` for all heads; ``y W_out``.  Products in ``dtype``; the
    L2 norms, ``b``, ``g``, the decays' sums, the chunk's inverse, the
    carried state and the norm's statistics float32; ``A_log`` and
    ``dt_bias`` float32 leaves.

    The chunks scanned and the bytes of the chunk states written are sown
    as counters (``delta_chunks``; ``delta_state_bytes``, float32: a step's
    can pass 2**31; ``delta_layers`` the layer calls)."""

    heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    neg_eigval: bool = False
    chunk: int = 64
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from tensorflowonspark_tpu.ops import gated_delta

        batch, seq, d_model = x.shape
        f32 = jnp.float32
        keys, values = self.heads * self.key_dim, self.heads * self.value_dim
        qkv, z, a, b = jnp.split(
            nn.Dense(2 * keys + 2 * values + 2 * self.heads, use_bias=False,
                     dtype=self.dtype, name="in_proj")(x),
            [2 * keys + values, 2 * keys + 2 * values,
             2 * keys + 2 * values + self.heads], axis=-1)
        taps = self.param("conv", nn.initializers.normal(0.02),
                          (self.conv_kernel, 2 * keys + values))
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, f32, 1.0, 16.0)), (self.heads,))
        dt_bias = self.param("dt_bias", _inverse_softplus_steps(),
                             (self.heads,))
        scale = self.param("norm", nn.initializers.ones, (self.value_dim,))
        with jax.named_scope("conv"):
            qkv = nn.silu(_causal_taps(qkv, taps.astype(self.dtype)))
        q, k, v = jnp.split(qkv, [keys, 2 * keys], axis=-1)
        q = q.reshape(batch, seq, self.heads, self.key_dim)
        k = k.reshape(batch, seq, self.heads, self.key_dim)
        v = v.reshape(batch, seq, self.heads, self.value_dim)
        beta = jax.nn.sigmoid(b.astype(f32)) * (2.0 if self.neg_eigval
                                                else 1.0)
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            a.astype(f32) + dt_bias.astype(f32))
        with jax.named_scope("scan"):
            unit = lambda t: t.astype(f32) * jax.lax.rsqrt(  # noqa: E731
                jnp.square(t.astype(f32)).sum(-1, keepdims=True) + 1e-6)
            o = gated_delta.gated_delta_rule(
                (unit(q) * self.key_dim ** -0.5).astype(self.dtype),
                unit(k).astype(self.dtype), v, g, beta, chunk=self.chunk)
        chunks, state_bytes = gated_delta.chunk_counts(k, v, self.chunk)
        self.sow("intermediates", "counters", {
            "delta_chunks": jnp.asarray(chunks, jnp.int32),
            "delta_state_bytes": jnp.asarray(state_bytes, f32),
            "delta_layers": jnp.asarray(1, jnp.int32)})
        with jax.named_scope("gate_norm"):
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(
                jnp.square(o).mean(-1, keepdims=True) + self.norm_eps)
            y = (o * scale.astype(f32)).reshape(batch, seq, values) \
                * nn.silu(z.astype(f32))
        return nn.Dense(d_model, use_bias=False, dtype=self.dtype,
                        name="out_proj")(y.astype(self.dtype))


class Relu2(nn.Module):
    """``relu(x W_1) ** 2 W_2``, no biases, no gate."""

    hidden: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        return dense(x.shape[-1], "w2")(
            jnp.square(nn.relu(dense(self.hidden, "w1")(x))))


class SwiGLU(nn.Module):
    """``(silu(x W_1) * (x W_3)) W_2``, no biases."""

    hidden: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        return dense(x.shape[-1], "w2")(
            nn.silu(dense(self.hidden, "w1")(x)) * dense(self.hidden, "w3")(x))


_FEED_FORWARD = {"swiglu": SwiGLU, "relu2": Relu2}


class TopKExperts(nn.Module):
    """Top-k mixture of experts without dropped tokens
    (:func:`~tensorflowonspark_tpu.parallel.ep.route_topk`,
    :func:`~tensorflowonspark_tpu.parallel.ep.experts_ffn`): the router
    scores all ``num_experts`` by ``score`` (a sigmoid each, or a softmax
    over them all), an ``expert_bias`` leaf (there only where
    ``selection_bias``) enters the choice of the ``experts_per_token`` only,
    the chosen scores are renormalised where ``norm_topk`` and scaled.
    ``shared`` is the width of a shared expert (flax name ``shared``; 0:
    none) that every token passes through, added to the routed sum.
    ``act`` is the form of an expert and of the shared one: ``"swiglu"``,
    three matrices ``(silu(x W_1) * (x W_3)) W_2``, or ``"relu2"``, two,
    ``relu(x W_1) ** 2 W_2`` (no ``w3`` leaf).

    ``held = (first, count)`` says which of the router's experts this layer
    holds (``w1``/``w3 [count, D, F]``, ``w2 [count, F, D]``; None: all).  It
    routes over all of them and returns its own experts' part of the sum;
    what the absent experts would add is left out, which is one chip's part
    of an expert-parallel layer before the exchange (there is none here).
    The shared expert is computed whole by every holder: when the shares of
    a layer are added up it counts once.

    The token-slot counts of the call are sown as counters
    (``moe_slots_total``, ``moe_slots_local``, the heaviest and the mean
    held expert's count as ``moe_expert_load_max_sum`` and
    ``moe_expert_load_mean_sum``, ``moe_layers_steps`` the layer calls),
    and beside them the row tiles of a sorted buffer that the passes between
    the grouped products visit of those there are (``moe_gate_tiles_live``,
    ``moe_gate_tiles_total``: ``moe_slots_local / moe_slots_total`` rounded
    up to a tile)."""

    num_experts: int
    experts_per_token: int
    hidden: int
    held: Optional[Tuple[int, int]] = None
    norm_topk: bool = True
    routed_scaling: float = 1.0
    score: str = "sigmoid"
    selection_bias: bool = True
    shared: int = 0
    act: str = "swiglu"
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from tensorflowonspark_tpu.parallel import ep as ep_mod

        batch, seq, d_model = x.shape
        first, count = self.held or (0, self.num_experts)
        init = nn.initializers.lecun_normal()
        router = self.param("router", init, (d_model, self.num_experts))
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (self.num_experts,)) if self.selection_bias else None
        w1 = self.param("w1", init, (count, d_model, self.hidden))
        w3 = self.param("w3", init, (count, d_model, self.hidden)) \
            if self.act == "swiglu" else None
        w2 = self.param("w2", init, (count, self.hidden, d_model))
        tokens = x.reshape(batch * seq, d_model)
        with jax.named_scope("route"):
            sel, weights = ep_mod.route_topk(
                tokens, router, bias, self.experts_per_token,
                norm_topk=self.norm_topk, scaling=self.routed_scaling,
                score=self.score)
        y, load = ep_mod.experts_ffn(tokens, sel, weights, w1, w3, w2, first,
                                     dtype=self.dtype, act=self.act)
        self.sow("intermediates", "counters", {
            "moe_slots_total": load["slots_total"],
            "moe_slots_local": load["slots_local"],
            "moe_expert_load_max_sum": load["expert_load_max"],
            "moe_expert_load_mean_sum": load["expert_load_mean"],
            "moe_gate_tiles_live": load["gate_tiles_live"],
            "moe_gate_tiles_total": load["gate_tiles_total"],
            "moe_layers_steps": jnp.asarray(1, jnp.int32)})
        y = y.reshape(batch, seq, d_model)
        if self.shared:
            y = y + _FEED_FORWARD[self.act](self.shared, self.dtype,
                                            name="shared")(x)
        return y



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
    spec: LayerSpec           # the layer's description
    attention: str = "full"
    ep_mode: str = "gspmd"    # gspmd | shard_map (see MoEMlp)
    mesh: Optional[object] = None
    ep_batch_axes: Optional[tuple] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        spec = self.spec
        for kind, part in ((spec.op, self._op), (spec.ff, self._ff)):
            if kind == "none":
                continue
            norm = _norm(spec.norm, spec.norm_eps, self.dtype)
            if spec.norm_place == "output":
                x = x + norm(part(spec, x))
            else:
                x = x + part(spec, norm(x))
        return x

    @nn.nowrap
    def _op(self, spec, h):
        if spec.op == "conv":
            return ShortConv(spec.conv_kernel, self.dtype,
                             name="short_conv")(h)
        if spec.op == "mamba2":
            return Mamba2(spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state,
                          spec.ssm_groups, spec.conv_kernel, spec.ssm_chunk,
                          spec.norm_eps, self.dtype, name="mamba")(h)
        if spec.op == "gated_delta":
            return GatedDelta(spec.delta_heads, spec.delta_key_dim,
                              spec.delta_value_dim, spec.conv_kernel,
                              spec.delta_neg_eigval, spec.delta_chunk,
                              spec.norm_eps, self.dtype, name="delta")(h)
        # the fused form keeps flax's own name (Attention_0: checkpoints
        # of the GPT-2 decoder), the grouped-query and latent forms are
        # "attention"
        latent = spec if spec.op == "mla" else None
        return Attention(
            spec.num_heads, spec.head_dim, self.attention, self.mesh,
            self.dtype, num_kv_heads=spec.num_kv_heads,
            qk_norm=spec.qk_norm, norm_eps=spec.norm_eps,
            rope_theta=(spec.rope_theta if spec.positions == "rope"
                        else None),
            rope_yarn=spec.rope_yarn, flash_block=spec.flash_block,
            window=spec.window, latent=latent,
            index_heads=spec.index_heads, index_dim=spec.index_dim,
            index_topk=spec.index_topk,
            name=None if spec.num_kv_heads is None and not latent
            else "attention")(h)

    @nn.nowrap
    def _ff(self, spec, h):
        if spec.ff == "switch":
            return MoEMlp(num_experts=spec.num_experts,
                          mlp_ratio=spec.ff_size // h.shape[-1],
                          capacity_factor=spec.capacity_factor,
                          ep_mode=self.ep_mode, mesh=self.mesh,
                          ep_batch_axes=self.ep_batch_axes,
                          dtype=self.dtype, name="moe")(h)
        if spec.ff == "experts":
            return TopKExperts(
                num_experts=spec.num_experts,
                experts_per_token=spec.experts_per_token,
                hidden=spec.expert_size, held=spec.held_experts,
                norm_topk=spec.norm_topk, routed_scaling=spec.routed_scaling,
                score=spec.router_score, selection_bias=spec.selection_bias,
                shared=spec.shared_size, act=spec.expert_act,
                dtype=self.dtype, name="moe")(h)
        if spec.ff in _FEED_FORWARD:
            return _FEED_FORWARD[spec.ff](spec.ff_size, self.dtype,
                                          name="mlp")(h)
        d_model = h.shape[-1]
        h = nn.Dense(spec.ff_size, dtype=self.dtype)(h)
        h = nn.gelu(h)
        return nn.Dense(d_model, dtype=self.dtype)(h)


class TransformerLM(nn.Module):
    spec: DecoderSpec         # the decoder's description
    attention: str = "full"
    ep_mode: str = "gspmd"    # gspmd | shard_map (see MoEMlp)
    mesh: Optional[object] = None
    ep_batch_axes: Optional[tuple] = None
    remat: bool = False
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        spec = self.spec
        x = nn.Embed(spec.vocab_size, spec.hidden_size, dtype=self.dtype,
                     name="embed")(tokens)
        if spec.learned_positions:
            pos = nn.Embed(spec.learned_positions, spec.hidden_size,
                           dtype=self.dtype,
                           name="pos_embed")(jnp.arange(tokens.shape[1]))
            x = x + pos[None]
        # remat trades FLOPs for HBM: each block's activations (incl. the
        # full-attention S x S probs the backward pass would otherwise
        # keep per layer) are recomputed during backprop instead of
        # stored — the standard TPU recipe for configs whose stored
        # activations exceed HBM (e.g. d2048 x 16L x b16 full attention).
        # Kept beside a block's input [B, S, hidden] are the residuals that
        # cost a kernel run to make again, by the names the ops give them
        # and wherever the trace holds such a name: under "flash" the
        # kernel's output and logsumexp rows (B S H dv elements of the
        # compute dtype and B H S float32 a layer), and an indexed layer's
        # key bits and index logsumexp (S S / 8 bytes and S float32 a batch
        # row), and a Mamba-2 layer's scan output and chunk states (B S H P
        # elements of the compute dtype each, at chunks as long as the state
        # is wide; its projections and convolution are made again).
        # Everything else in the block is recomputed.
        block_cls = Block
        if self.remat:
            from tensorflowonspark_tpu.ops import KEPT

            block_cls = nn.remat(
                Block, policy=jax.checkpoint_policies.save_only_these_names(
                    *KEPT))
        for i, layer in enumerate(spec.layers):
            x = block_cls(spec=layer, attention=self.attention,
                          ep_mode=self.ep_mode, mesh=self.mesh,
                          ep_batch_axes=self.ep_batch_axes, dtype=self.dtype,
                          name="block_%d" % i)(x)
        x = _norm(spec.norm, spec.norm_eps, self.dtype)(x)
        if not spec.tied_readout:
            head = self.param("head", nn.initializers.normal(0.02),
                              (spec.hidden_size, spec.vocab_size))
            return (x @ head.astype(self.dtype)).astype(jnp.float32)
        # weight-tied readout keeps the big vocab matmul on the MXU once
        embed = self.variables["params"]["embed"]["embedding"]
        return (x @ embed.T.astype(self.dtype)).astype(jnp.float32)


@register_model("transformer_lm")
def build_transformer(vocab_size=32000, num_layers=4, num_heads=8,
                      head_dim=64, max_seq_len=2048, attention="full",
                      mlp="dense", num_experts=8, capacity_factor=1.25,
                      ep_mode="gspmd", mesh=None, ep_batch_axes=None,
                      remat=False, dtype="float32"):
    spec = gpt2_spec(vocab_size, num_layers, num_heads, head_dim, max_seq_len,
                     mlp=mlp, num_experts=num_experts,
                     capacity_factor=capacity_factor)
    return TransformerLM(spec=spec, attention=attention, ep_mode=ep_mode,
                         mesh=mesh, ep_batch_axes=ep_batch_axes, remat=remat,
                         dtype=jnp.dtype(dtype))


def register_decoder(name):
    """Decorator of a family's spec function (``config`` -> a
    :class:`DecoderSpec`; ``models/families/``): registers with ``get_model``
    under ``name`` the one decoder under ``spec_fn(config)``, at the
    run-time choices of ``transformer_lm`` (``attention`` picks the
    contraction).  Returns the spec function as it came."""
    def deco(spec_fn):
        def build(config, attention="flash", mesh=None, remat=False,
                  dtype="float32"):
            return TransformerLM(spec=spec_fn(config), attention=attention,
                                 mesh=mesh, remat=remat,
                                 dtype=jnp.dtype(dtype))

        register_model(name)(build)
        return spec_fn
    return deco


def _sown(tree, name):
    """Every value sown under ``name`` anywhere in the intermediates tree."""
    found = []
    if isinstance(tree, dict):
        for key, val in tree.items():
            if key == name:
                found.extend(val if isinstance(val, (tuple, list)) else (val,))
            else:
                found.extend(_sown(val, name))
    return found


def _sum_moe_aux(tree):
    """Sum every ``moe_aux_loss`` sown anywhere in the intermediates tree;
    None when the model has no MoE layers."""
    found = _sown(tree, "moe_aux_loss")
    return sum(found) if found else None


def _sum_counters(tree):
    """Every ``counters`` dict sown anywhere in the intermediates tree (one
    a layer call that counts), added up key by key, each key in the dtype it
    was sown in; empty when no layer counts."""
    total = {}
    for counts in _sown(tree, "counters"):
        for key, val in counts.items():
            total[key] = total[key] + val if key in total else val
    return total


def loss_fn(model, moe_aux_weight=0.01):
    """Next-token cross-entropy with per-row masking.

    The model is applied to the *full* sequence (not ``tokens[:, :-1]``) so
    the sequence length stays divisible by the mesh's ``seq`` axis for
    ring/ulysses attention; the last position, which has no target, is
    excluded via a position mask instead.

    MoE models' sown load-balance auxiliaries are folded in with weight
    ``moe_aux_weight`` (Switch Transformer's alpha=0.01 default) and
    reported via ``aux["moe_aux_loss"]``.  Layers with an index over the
    keys sow that index's loss: it is added to the step's loss as it is (its
    gradient reaches the index's leaves alone) and reported as
    ``aux["dsa_index_loss"]``.

    ``aux["counters"]``: what the layers that count sowed as ``counters``
    (device scalars under the names ``train.Trainer`` publishes them by,
    added up over the layers; the ``Trainer`` adds them up over the steps
    without a host sync), absent when no layer counts.  A layer states its
    own keys where it sows them; the one key added here is the index's loss,
    detached, as ``dsa_index_loss``.
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
        sown = dict(state.get("intermediates", {}))
        lb = _sum_moe_aux(sown)
        if lb is not None:
            aux["moe_aux_loss"] = lb
            ce = ce + moe_aux_weight * lb
        counters = _sum_counters(sown)
        index_losses = _sown(sown, "dsa_index_loss")    # [B] a layer
        if index_losses:
            index_loss = (sum(index_losses) * mask).sum() / jnp.maximum(
                mask.sum(), 1.0)
            aux["dsa_index_loss"] = index_loss
            counters["dsa_index_loss"] = jax.lax.stop_gradient(index_loss)
            ce = ce + index_loss
        if counters:
            aux["counters"] = counters
        return ce, aux

    return loss
