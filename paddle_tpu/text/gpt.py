"""GPT model family — the flagship (reference fixture: python/paddle/fluid/tests/
unittests/auto_parallel_gpt_model.py; fleet GPT entrypoints).

Written with framework layers only. Distributed execution does NOT rewrite this
model: fleet.distributed_model() attaches GSPMD PartitionSpecs to its parameters
(qkv/ffn column-sharded, proj row-sharded on the 'mp' axis — Megatron layout) and
pjit inserts the collectives. That is the TPU-native answer to the reference's
ColumnParallelLinear/RowParallelLinear program surgery.
"""
from __future__ import annotations

import math
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F

# --------------------------------------------------------- tensor parallelism
# The serving engine's sharded steps (serving/tp.py) run this model INSIDE a
# shard_map over a named mesh axis: every device holds a Megatron shard of
# the weights (qkv/fc1 column-split, out_proj/fc2 row-split) and of the
# paged KV pool (heads axis), and the row-parallel partial sums must be
# psum-reduced back to the replicated residual stream. The model code stays
# layout-agnostic — local head counts are derived from the actual weight
# shapes — and the ONLY tensor-parallel hook is this trace-time axis name:
# set by ``tp_axis(...)`` around the traced call, it makes the two
# row-parallel sites (attention out_proj, MLP fc2) and the LM head emit
# exactly one ``lax.psum`` each. None (the default) is a no-op on every
# single-chip path.
_TP_AXIS: str | None = None
# trace-time toggle for the EQuARX-style int8 logits all-reduce
# (serving/tp.py quantized_psum): set alongside the axis by tp_axis(...,
# quantized_logits=True); only the LM-head psum routes through it — the
# per-block residual psums stay exact f32
_TP_QUANTIZED: bool = False


@contextmanager
def tp_axis(name: str, quantized_logits: bool = False):
    """Trace-time context: the mesh axis name the model's row-parallel
    partial sums psum over (and whether the logits psum ships int8 codes
    instead of f32). Used by serving/tp.py around the shard_map'd
    engine steps; nested/exception-safe."""
    global _TP_AXIS, _TP_QUANTIZED
    prev = (_TP_AXIS, _TP_QUANTIZED)
    _TP_AXIS, _TP_QUANTIZED = name, bool(quantized_logits)
    try:
        yield
    finally:
        _TP_AXIS, _TP_QUANTIZED = prev


def _tp_psum(t: Tensor) -> Tensor:
    """Reduce a row-parallel partial sum across the tensor-parallel axis
    (identity outside a ``tp_axis`` context)."""
    if _TP_AXIS is None:
        return t
    import jax.lax as lax

    return Tensor(lax.psum(t._value, _TP_AXIS))


def _tp_logits(h: Tensor, weight: Tensor, transpose_y: bool) -> Tensor:
    """The LM head under tensor parallelism: the hidden (contraction) axis
    is split across the mesh — each device multiplies its OWN hidden slice
    of ``h`` against the matching slice of the replicated head weight, and
    ONE psum of the [.., vocab] partials reassembles the full logits. The
    head's FLOPs shard N ways at the cost of exactly one declared
    all-reduce — the "one for the logits" entry in the step's
    CollectiveBudget."""
    import jax.lax as lax

    hv, wv = h._value, weight._value
    n = lax.psum(1, _TP_AXIS)  # axis size: constant-folded, no collective
    i = lax.axis_index(_TP_AXIS)
    k = hv.shape[-1] // n
    h_loc = lax.dynamic_slice_in_dim(hv, i * k, k, axis=hv.ndim - 1)
    if transpose_y:  # tied wte [vocab, hidden]: slice its hidden columns
        w_loc = lax.dynamic_slice_in_dim(wv, i * k, k, axis=1)
        part = h_loc @ w_loc.T
    else:            # untied lm_head [hidden, vocab]: slice its rows
        w_loc = lax.dynamic_slice_in_dim(wv, i * k, k, axis=0)
        part = h_loc @ w_loc
    if _TP_QUANTIZED:
        # flag-gated int8 logits reduction: the single largest collective
        # payload (b*s*V f32) shrinks 4x; bit-identical when the flag is
        # off because this branch then never traces
        from ..serving.tp import quantized_psum
        return Tensor(quantized_psum(part, _TP_AXIS))
    return Tensor(lax.psum(part, _TP_AXIS))


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden: int = 0  # 0 -> 4*hidden
    max_seq_len: int = 1024
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    recompute: bool = False  # per-block rematerialization (jax.checkpoint)
    recompute_policy: str | None = None  # e.g. 'dots' = save MXU outputs only
    loss_chunk_size: int = 256  # rows per chunk in the fused head+CE scan

    def __post_init__(self):
        if not self.ffn_hidden:
            self.ffn_hidden = 4 * self.hidden_size


_PRESETS = {
    "gpt3-125m": dict(hidden_size=768, num_layers=12, num_heads=12),
    "gpt3-350m": dict(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt3-1.3b": dict(hidden_size=2048, num_layers=24, num_heads=16),
    "gpt3-2.7b": dict(hidden_size=2560, num_layers=32, num_heads=32),
    "gpt3-6.7b": dict(hidden_size=4096, num_layers=32, num_heads=32),
    "gpt3-13b": dict(hidden_size=5120, num_layers=40, num_heads=40),
}


def gpt_config(preset: str, **overrides) -> GPTConfig:
    cfg = dict(_PRESETS[preset])
    cfg.update(overrides)
    return GPTConfig(**cfg)


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h = cfg.hidden_size
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        attr = nn.ParamAttr(initializer=init)
        self.num_heads = cfg.num_heads
        self.head_dim = h // cfg.num_heads
        self.qkv_proj = nn.Linear(h, 3 * h, weight_attr=attr)
        self.out_proj = nn.Linear(h, h, weight_attr=attr)
        self.dropout = cfg.dropout

    def forward(self, x, attn_mask=None, cache=None, pos=None):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)
        # head count derived from the ACTUAL projection width, not the
        # config: inside a tensor-parallel shard_map the local qkv weight
        # holds num_heads / tp heads (serving/tp.py), and everything
        # downstream — attention, paged KV writes — runs on that local
        # slice. Single-chip, this is exactly self.num_heads.
        nh = qkv.shape[-1] // (3 * self.head_dim)
        qkv = qkv.reshape([b, s, 3, nh, self.head_dim])
        if cache is not None and "k_pool" in cache:
            return self._paged_forward(x, qkv, cache)
        qkv = qkv.transpose([2, 0, 3, 1, 4])  # 3, B, H, S, D
        q, k, v = qkv[0], qkv[1], qkv[2]
        if cache is not None:
            # Fixed-size KV cache for autoregressive decode: buffers are
            # [B, H, max_len, D] (static shapes — XLA-friendly), new keys are
            # written at `pos` via dynamic_update_slice and masked attention
            # covers exactly the written prefix. TPU-native answer to the
            # reference's growing fused-attention CacheKV
            # (operators/fused/fused_multi_transformer_op.cu concat path).
            import jax.lax as lax
            import jax.numpy as jnp

            k_buf, v_buf = cache["k"], cache["v"]
            p = pos._value if isinstance(pos, Tensor) else pos
            k_all = lax.dynamic_update_slice(k_buf, k._value.astype(k_buf.dtype),
                                             (0, 0, p, 0))
            v_all = lax.dynamic_update_slice(v_buf, v._value.astype(v_buf.dtype),
                                             (0, 0, p, 0))
            max_len = k_all.shape[2]
            j = jnp.arange(max_len)[None, :]
            i = jnp.arange(s)[:, None] + p
            mask = Tensor(j <= i)  # [s, max_len]: causal over the written prefix
            out = F.scaled_dot_product_attention(
                q, Tensor(k_all), Tensor(v_all), attn_mask=mask,
                dropout_p=0.0, is_causal=False, training=False,
            )
            out = out.transpose([0, 2, 1, 3]).reshape([b, s, h])
            return self.out_proj(out), {"k": k_all, "v": v_all}
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=self.dropout,
            is_causal=attn_mask is None, training=self.training,
        )
        out = out.transpose([0, 2, 1, 3]).reshape([b, s, h])
        return self.out_proj(out)

    def _paged_forward(self, x, qkv, cache):
        """Serving decode/prefill against a paged KV pool (kernels/
        paged_attention.py). The cache dict carries, besides the per-layer
        pools, the batch's page tables [B, pages_per_seq], ctx_lens [B]
        (tokens resident before this call) and valid [B, s] (which of the s
        new tokens are real — padding and inactive slots write to the
        reserved null page 0 instead of corrupting live pages)."""
        import jax.numpy as jnp

        from ..kernels import paged_attention as pa

        b, s, h = x.shape
        k_pool, v_pool = cache["k_pool"], cache["v_pool"]
        ctx = cache["ctx_lens"].astype(jnp.int32)  # [B]
        table = cache["page_table"]  # [B, pages_per_seq]
        valid = cache["valid"]  # [B, s] bool
        page_size = k_pool.shape[1]
        qkv_v = qkv._value  # [B, s, 3, H, D]
        q = jnp.transpose(qkv_v[:, :, 0], (0, 2, 1, 3))  # [B, H, s, D]
        k_new = qkv_v[:, :, 1]  # [B, s, H, D]
        v_new = qkv_v[:, :, 2]
        positions = ctx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        # clamp the page lookup explicitly: a multi-token decode-style call
        # (the speculative-verify step writes s = depth+1 tokens at
        # ctx..ctx+depth) may form positions past the table width on rows
        # whose ctx is garbage (inactive slots) — those writes are routed
        # to the null page by `valid` below, but the INDEX itself must
        # stay in range rather than rely on gather clip semantics
        page_idx = jnp.minimum(positions // page_size, table.shape[1] - 1)
        page_ids = jnp.take_along_axis(table, page_idx, axis=1)
        page_ids = jnp.where(valid, page_ids, 0)  # dead writes -> null page
        offsets = jnp.where(valid, positions % page_size, 0)
        if "k_scale" in cache:
            # int8-quantized pool: quantize at scatter time (per-page-per-
            # head absmax scales), dequantize inside the attention gather —
            # the ragged mask, page tables, and everything downstream stay
            # byte-for-byte layout-blind (serving/kv_cache.py kv_dtype)
            with jax.named_scope("kv_write"):
                k_pool, v_pool, k_sc, v_sc = pa.paged_write_quant(
                    k_pool, v_pool, cache["k_scale"], cache["v_scale"],
                    k_new, v_new, page_ids, offsets)
            out = pa.paged_attention(q, k_pool, v_pool, table, ctx,
                                     k_scale=k_sc, v_scale=v_sc)
            scales = {"k_scale": k_sc, "v_scale": v_sc}
        else:
            with jax.named_scope("kv_write"):
                k_pool, v_pool = pa.paged_write(k_pool, v_pool, k_new, v_new,
                                                page_ids, offsets)
            out = pa.paged_attention(q, k_pool, v_pool, table, ctx)
            scales = {}
        # -1, not h: under tensor parallelism the local heads span h / tp
        # and the row-parallel out_proj contracts that local width
        out = Tensor(jnp.transpose(out, (0, 2, 1, 3)).reshape(b, s, -1)
                     .astype(x._value.dtype))
        new_cache = dict(cache, k_pool=k_pool, v_pool=v_pool, **scales,
                         ctx_lens=ctx + jnp.sum(valid, axis=1,
                                                dtype=jnp.int32))
        # row-parallel out_proj under tensor parallelism: each device
        # contracts its local heads; the psum restores the full projection
        # (the per-block attention all-reduce in the step's budget)
        return _tp_psum(self.out_proj(out)), new_cache


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        attr = nn.ParamAttr(initializer=init)
        self.fc1 = nn.Linear(cfg.hidden_size, cfg.ffn_hidden, weight_attr=attr)
        self.fc2 = nn.Linear(cfg.ffn_hidden, cfg.hidden_size, weight_attr=attr)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x):
        # row-parallel fc2 under tensor parallelism: fc1 is column-split
        # (gelu is elementwise, so the split needs no communication), fc2
        # contracts the local ffn shard — the psum of the partials is the
        # per-block MLP all-reduce in the step's budget
        return self.dropout(
            _tp_psum(self.fc2(F.gelu(self.fc1(x), approximate=True))))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.mlp = GPTMLP(cfg)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x, attn_mask=None, cache=None, pos=None):
        # block/attn and block/mlp (LayerNorm and residual add included)
        # reach every operation's op_name: metadata only, the compiled
        # program is the same
        if cache is not None:
            with jax.named_scope("block/attn"):
                a, new_cache = self.attn(self.ln1(x), attn_mask,
                                         cache=cache, pos=pos)
                x = x + a
            with jax.named_scope("block/mlp"):
                x = x + self.mlp(self.ln2(x))
            return x, new_cache
        with jax.named_scope("block/attn"):
            x = x + self.dropout(self.attn(self.ln1(x), attn_mask))
        with jax.named_scope("block/mlp"):
            x = x + self.mlp(self.ln2(x))
        return x


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def init_cache(self, batch_size: int, max_len: int | None = None, dtype=None):
        """Per-layer fixed-size KV buffers for `forward(caches=..., pos=...)`."""
        import jax.numpy as jnp

        c = self.cfg
        max_len = max_len or c.max_seq_len
        head_dim = c.hidden_size // c.num_heads
        dt = dtype or self.wte.weight._value.dtype
        shape = (batch_size, c.num_heads, max_len, head_dim)
        return [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
                for _ in range(c.num_layers)]

    def forward(self, input_ids, attn_mask=None, position_ids=None,
                caches=None, pos=None):
        import paddle_tpu as P

        b, s = input_ids.shape
        if position_ids is None:
            position_ids = P.arange(s, dtype="int64").unsqueeze(0)
            if caches is not None and "k_pool" in caches[0]:
                # paged serving path: every slot decodes at its own length;
                # clip keeps dead slots' garbage positions inside the table
                import jax.numpy as jnp

                ctx = caches[0]["ctx_lens"]
                posn = ctx[:, None] + jnp.arange(s, dtype=ctx.dtype)[None, :]
                position_ids = Tensor(
                    jnp.clip(posn, 0, self.cfg.max_seq_len - 1))
            elif caches is not None:
                p = pos._value if isinstance(pos, Tensor) else pos
                position_ids = Tensor(position_ids._value + p)
            else:
                from ..distributed.sequence_parallel import sp_local_offset

                off = sp_local_offset(s)  # global positions when sequence-parallel
                if not isinstance(off, int) or off != 0:
                    position_ids = position_ids + off
        with jax.named_scope("embed"):
            x = self.wte(input_ids) + self.wpe(position_ids)
            x = self.drop(x)
        if caches is not None:
            if attn_mask is not None:
                raise NotImplementedError(
                    "attn_mask is not supported on the KV-cache path: the "
                    "cache builds its own causal-prefix mask. Left-padded "
                    "batches are not yet handled — right-pad prompts instead.")
            new_caches = []
            for blk, cache in zip(self.blocks, caches):
                x, nc = blk(x, None, cache=cache, pos=pos)
                new_caches.append(nc)
            with jax.named_scope("final_norm"):
                return self.ln_f(x), new_caches
        if self.cfg.recompute:
            from ..distributed.fleet.recompute import recompute

            for blk in self.blocks:
                x = (recompute(blk, x, policy=self.cfg.recompute_policy)
                     if attn_mask is None else blk(x, attn_mask))
        else:
            for blk in self.blocks:
                x = blk(x, attn_mask)
        with jax.named_scope("final_norm"):
            return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if cfg.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size, bias_attr=False)

    def forward(self, input_ids, labels=None, attn_mask=None,
                caches=None, pos=None):
        if caches is not None:
            if labels is not None:
                raise NotImplementedError(
                    "labels (training loss) cannot be combined with the "
                    "KV-cache decode path")
            h, new_caches = self.gpt(input_ids, attn_mask, caches=caches, pos=pos)
            from ..tensor_ops.math import matmul

            with jax.named_scope("lm_head"):
                if _TP_AXIS is not None:
                    # hidden-contraction-sharded LM head: one all-reduce of
                    # the logits, head FLOPs split across the mesh
                    w = (self.lm_head.weight if self.lm_head is not None
                         else self.gpt.wte.weight)
                    return _tp_logits(h, w, self.lm_head is None), new_caches
                if self.lm_head is not None:
                    return self.lm_head(h), new_caches
                return matmul(h, self.gpt.wte.weight,
                              transpose_y=True), new_caches
        h = self.gpt(input_ids, attn_mask)
        if labels is not None:
            # Fused head+CE: scans vocab projection in sequence chunks so the
            # [b, s, vocab] logits (3.3 GB fp32 at b16/s1024/v50k) never hit HBM.
            with jax.named_scope("head_ce"):
                if self.lm_head is not None:
                    return F.linear_cross_entropy(
                        h, self.lm_head.weight, labels,
                        chunk_size=self.cfg.loss_chunk_size)
                return F.linear_cross_entropy(
                    h, self.gpt.wte.weight, labels, transpose_y=True,
                    chunk_size=self.cfg.loss_chunk_size)
        if self.lm_head is not None:
            logits = self.lm_head(h)
        else:
            from ..tensor_ops.math import matmul

            logits = matmul(h, self.gpt.wte.weight, transpose_y=True)
        return logits

    # ---------------------------------------------------- serving contract
    def paged_cache_spec(self, kv_dtype: str = "float32",
                         tensor_parallel: int = 1,
                         speculative: bool = False):
        """What this model keeps in a paged cache, for ``ServingEngine``:
        keys and values with a heads axis, ``k_pool`` and ``v_pool`` of
        ``[heads, head_dim]`` a token in the weights' dtype (int8 codes
        beside per-page-per-head scales under ``kv_dtype="int8"``). Every
        mode the engine has is supported."""
        from ..kernels.paged_attention import pages_staged_fn
        from ..serving.kv_cache import PagedCacheSpec, kv_heads_leaves

        c = self.cfg
        dtype = self.gpt.wte.weight._value.dtype
        quantized = kv_dtype == "int8"
        head_dim = c.hidden_size // c.num_heads

        def pages_staged(num_query_tokens, pages_per_seq, page_size):
            # a device's own heads: what its kernel launch resolves from
            return pages_staged_fn(
                head_dim, c.num_heads // tensor_parallel, page_size,
                pages_per_seq, num_query_tokens, quantized=quantized,
                q_itemsize=dtype.itemsize)

        return PagedCacheSpec(
            num_layers=c.num_layers, max_seq_len=c.max_seq_len, dtype=dtype,
            leaves=kv_heads_leaves(c.num_heads, head_dim, dtype,
                                   quantized=quantized),
            pages_staged=pages_staged)

    def decode_kernel_eligible(self, pages_per_seq: int, page_size: int,
                               quantized: bool = False) -> bool:
        """Whether the unified ragged kernel is dispatchable for a decode
        step of this model: the single ``decode_kernel_eligible`` gate."""
        from ..kernels import paged_attention as _pa
        from ..kernels._common import on_tpu_backend
        from ..utils.flags import flag

        c = self.cfg
        return _pa.decode_kernel_eligible(
            c.hidden_size // c.num_heads, pages_per_seq, page_size,
            num_heads=c.num_heads, quantized=quantized,
            on_tpu=on_tpu_backend(),
            flags_on=bool(flag("FLAGS_use_pallas_kernels", True)))[0]

    def generate(self, input_ids, **kwargs):
        """KV-cache autoregressive decoding — see text/generation.py."""
        from .generation import generate

        return generate(self, input_ids, **kwargs)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def loss_flops_per_token(self):
        return self.flops_per_token()

    def flops_per_token(self) -> float:
        """Approximate training FLOPs/token (6*N + attention), for MFU accounting."""
        c = self.cfg
        n = self.num_params()
        attn = 6 * c.num_layers * c.hidden_size * c.max_seq_len  # 2*2*L*h*s fw+bw-ish
        return 6.0 * n + attn


# --------------------------------------------------------------- pipeline form
class GPTEmbeddingPipe(nn.Layer):
    """Stage-0 prologue for PipelineLayer GPT (token + position embedding)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = nn.initializer.Normal(0.0, cfg.initializer_range)
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, input_ids):
        import paddle_tpu as P

        s = input_ids.shape[1]
        pos = P.arange(s, dtype="int64").unsqueeze(0)
        return self.drop(self.wte(input_ids) + self.wpe(pos))


class GPTHeadPipe(nn.Layer):
    """Last-stage epilogue: final LN + LM head (untied for pipeline)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size, bias_attr=False)

    def forward(self, x):
        return self.lm_head(self.ln_f(x))


class GPTPipeLoss(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.vocab = cfg.vocab_size

    def forward(self, logits, labels):
        return F.cross_entropy(logits.reshape([-1, self.vocab]), labels.reshape([-1]))


def build_gpt_pipeline(cfg: GPTConfig, num_stages: int, topology=None):
    """GPT as a PipelineLayer (reference: fleet GPT with PipelineLayer descs,
    seg_method 'layer:GPTBlock')."""
    from ..distributed.fleet.meta_parallel import LayerDesc, PipelineLayer

    descs = [LayerDesc(GPTEmbeddingPipe, cfg)]
    descs += [LayerDesc(GPTBlock, cfg) for _ in range(cfg.num_layers)]
    descs += [LayerDesc(GPTHeadPipe, cfg)]
    return PipelineLayer(descs, num_stages=num_stages, topology=topology,
                         loss_fn=GPTPipeLoss(cfg))
