"""Mellum 2 (``model_type`` ``mellum``; ``JetBrains/Mellum2-12B-A2.5B-
Instruct``): a pre-RMSNorm decoder whose every block is grouped-head
attention, causal in a ``full_attention`` layer and causal-and-within-
``sliding_window`` in a ``sliding_attention`` layer (``layer_types``), with
rotary positions whose parameters differ by layer kind
(``rope_parameters``: plain in window layers, YaRN with its
``attention_factor`` on cos and sin in full ones), followed by a
softmax-routed mixture of small gated-SiLU experts (top-k of the softmax
over all, renormalised, no shared expert); a final RMSNorm and an untied
head with float32 logits. ``tests/refs/mellum_reference.py`` has the
equations and what the config leaves open (no QK-norm, no MTP head).

Serving only: ``forward(ids)`` is the whole pass, and ``forward(ids,
caches=...)`` the paged-cache contract that ``serving.ServingEngine`` calls
(see ``paged_cache_spec``). Every layer keeps ``k_pool`` / ``v_pool`` of
``kv_heads x head_dim`` values a token, flat (whole 128-lane rows), but in
two page GROUPS by layer kind (``serving.kv_cache.PageGroup``): the
``full`` group keeps a context's every page, the ``window`` group's pages
are freed behind the window, so each group has its own page table and a
layer reads its own. A call of one token a row (decode) runs
``gqa_decode_attention``, in a window layer over the chunks inside the
window only. A call of several (a prefill) from position 0 runs the
grouped flash forward over the prompt's own q, k, v
(``kernels.flash_attention.flash_fwd_grouped``: blocks behind the window
skipped); behind cached tokens (a chunk's tail) the composite over the
pool, a block of queries at a time.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..incubate.distributed.models import dropless_moe as dm
from .kimi_k2 import yarn_inv_freq

__all__ = ["MellumConfig", "MellumForCausalLM", "MOE_COUNTERS",
           "rotary_tables"]

#: the step counters a paged call reports (``new_cache["counters"]`` of a
#: layer, int32 [4]), under the names the engine publishes them
MOE_COUNTERS = tuple(f"moe_{n}_total" for n in dm.COUNTERS)

WINDOW, FULL = "sliding_attention", "full_attention"


def _layer_types():
    return [FULL if i % 4 == 3 else WINDOW for i in range(28)]


def _rope_parameters():
    return {FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                   "original_max_position_embeddings": 8192,
                   "beta_fast": 32, "beta_slow": 1,
                   "attention_factor": 1.2772588722239782},
            WINDOW: {"rope_type": "default", "rope_theta": 500000}}


@dataclass
class MellumConfig:
    """The model's ``config.json``, key for key; the defaults are
    Mellum2-12B-A2.5B-Instruct's published values."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168       # unused: no layer is dense
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    layer_types: list = field(default_factory=_layer_types)
    mlp_layer_types: list | None = None  # None: "sparse" in every layer
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_parameters: dict = field(default_factory=_rope_parameters)
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    max_window_layers: int = 0
    use_sliding_window: bool = True
    initializer_range: float = 0.02

    def __post_init__(self):
        self.layer_types = list(self.layer_types)
        if self.mlp_layer_types is None:
            self.mlp_layer_types = ["sparse"] * self.num_hidden_layers
        self.mlp_layer_types = list(self.mlp_layer_types)
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {WINDOW, FULL}:
            raise ValueError(f"layer_types names {WINDOW!r} or {FULL!r} for "
                             "each of num_hidden_layers layers")
        if self.mlp_layer_types != ["sparse"] * self.num_hidden_layers:
            raise ValueError("only mlp_layer_types 'sparse' in every layer "
                             "is here (as published: no layer is dense)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads do not group over the KV heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary pairs)")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")
        if self.sliding_window < 1:
            raise ValueError("sliding_window < 1")
        if self.tie_word_embeddings or self.attention_bias \
                or self.hidden_act != "silu":
            raise ValueError("only the untied head, no attention bias and "
                             "silu are here (as published)")
        for kind in set(self.layer_types):
            rp = self.rope_parameters[kind]
            if rp["rope_type"] not in ("default", "yarn"):
                raise ValueError(f"rope_type {rp['rope_type']!r} of {kind}")

    # what the serving engine reads of any model's config
    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    def window_of(self, kind: str) -> int | None:
        return self.sliding_window if kind == WINDOW else None


# ------------------------------------------------------------- positions
def rotary_tables(positions, cfg: MellumConfig) -> dict:
    """``{layer kind: (cos, sin)}``, float32 ``[b, s, head_dim]``, for the
    kinds the model has: a window layer's plain table at its theta; a full
    layer's YaRN blend of ``inv_freq`` and ``inv_freq / factor`` (static:
    it applies at every length) with ``attention_factor`` multiplied into
    cos and sin."""
    out = {}
    for kind in dict.fromkeys(cfg.layer_types):
        rp = cfg.rope_parameters[kind]
        yarn = rp["rope_type"] == "yarn"
        inv = yarn_inv_freq(cfg.head_dim, float(rp["rope_theta"]),
                            rp if yarn else None)
        ang = positions.astype(jnp.float32)[..., None] * inv
        ang = jnp.concatenate([ang, ang], axis=-1)
        t = float(rp["attention_factor"]) if yarn else 1.0
        out[kind] = (jnp.cos(ang) * t, jnp.sin(ang) * t)
    return out


def _rotate(x, cos, sin):
    """Rotate-half in float32: x [b, s, heads, d]; cos, sin [b, s, d]."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[:, :, None] + turned * sin[:, :, None]).astype(x.dtype)


def _rms_norm(x, w, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------ layers
class _Weight(nn.Layer):
    """One matrix ``[in, out]`` (or one vector) under the name ``weight``."""

    def __init__(self, shape, std: float | None):
        super().__init__()
        init = (nn.initializer.Constant(1.0) if std is None
                else nn.initializer.Normal(0.0, std))
        self.weight = self.create_parameter(
            shape, attr=nn.ParamAttr(initializer=init))

    @property
    def w(self):
        return self.weight._value


def _flash_prefill(s: int, d: int) -> tuple[bool, bool]:
    """``(use, interpret)``: whether a prefill of ``s`` tokens a row from
    position 0 runs the grouped flash forward."""
    from ..kernels._common import on_tpu_backend
    from ..kernels.flash_attention import grouped_supported
    from ..utils.flags import flag

    interp = bool(flag("FLAGS_ragged_interpret", False))
    use = bool(flag("FLAGS_use_pallas_kernels", True)) \
        and (on_tpu_backend() or interp) and grouped_supported(s, d, interp)
    return use, interp


class MellumAttention(nn.Layer):
    def __init__(self, cfg: MellumConfig, kind: str):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        h, d, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        self.q_proj = _Weight((h, cfg.num_attention_heads * d), std)
        self.k_proj = _Weight((h, cfg.kv_width), std)
        self.v_proj = _Weight((h, cfg.kv_width), std)
        self.o_proj = _Weight((cfg.num_attention_heads * d, h), std)

    def forward(self, y, rope, cache=None):
        from ..kernels import paged_attention as pa

        c = self.cfg
        b, s, _ = y.shape
        d, scale = c.head_dim, c.head_dim ** -0.5
        window = c.window_of(self.kind)
        q = jnp.matmul(y, self.q_proj.w).reshape(b, s, -1, d)
        k = jnp.matmul(y, self.k_proj.w).reshape(b, s, -1, d)
        v = jnp.matmul(y, self.v_proj.w).reshape(b, s, -1, d)
        with jax.named_scope("rope"):
            q, k = _rotate(q, *rope), _rotate(k, *rope)
        q = q.transpose(0, 2, 1, 3)                           # [b,H,s,d]
        if cache is None:
            o = _whole_attention(q, k, v, scale, window)
            new_cache = None
        else:
            k_pool, v_pool = cache["k_pool"], cache["v_pool"]
            ctx = cache["ctx_lens"].astype(jnp.int32)
            table, valid = cache["page_table"], cache["valid"]
            page_size = k_pool.shape[1]
            positions = ctx[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
            # as GPT's paged write: the index stays inside the table, dead
            # writes (padding, inactive slots) go to the null page
            page_idx = jnp.minimum(positions // page_size,
                                   table.shape[1] - 1)
            page_ids = jnp.take_along_axis(table, page_idx, axis=1)
            page_ids = jnp.where(valid, page_ids, 0)
            offsets = jnp.where(valid, positions % page_size, 0)
            with jax.named_scope("kv_write"):
                # a token's KV heads side by side: whole 128-lane rows
                k_pool, v_pool = pa.paged_write(
                    k_pool, v_pool, k.reshape(b, s, -1),
                    v.reshape(b, s, -1), page_ids, offsets)

            def over_pool():
                # a decode step: the grouped-head kernel, inside the
                # window only; several tokens a row: the composite
                return pa.paged_attention(q, k_pool, v_pool, table, ctx,
                                          scale=scale, window=window)

            flash, interpret = (False, False) if s == 1 \
                else _flash_prefill(s, d)
            if flash:
                from ..kernels.flash_attention import flash_fwd_grouped

                # from position 0 every key a query sees is in this call:
                # the prompt's own q, k, v, no pool read. Behind cached
                # tokens (a chunk's tail) the pool holds the rest
                o = jax.lax.cond(
                    jnp.all(ctx == 0),
                    lambda: flash_fwd_grouped(
                        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                        scale, window, interpret),
                    over_pool)
            else:
                o = over_pool()
            new_cache = dict(cache, k_pool=k_pool, v_pool=v_pool,
                             ctx_lens=ctx + jnp.sum(valid, axis=1,
                                                    dtype=jnp.int32))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1).astype(y.dtype)
        return jnp.matmul(o, self.o_proj.w), new_cache


def _whole_attention(q, k, v, scale: float, window: int | None):
    """The whole pass's attention, composite: q [b, H, s, d]; k, v [b, s,
    kv, d]; float32 scores and softmax."""
    b, heads, s, d = q.shape
    g = heads // k.shape[2]
    scores = jnp.einsum("bkgqd,btkd->bkgqt", q.reshape(b, -1, g, s, d), k,
                        preferred_element_type=jnp.float32) * scale
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    out = jnp.einsum("bkgqt,btkd->bkgqd", probs.astype(q.dtype), v)
    return out.reshape(b, heads, s, d)


class MellumExperts(nn.Layer):
    """Every expert's weights, stacked: ``[experts, in, out]``."""

    def __init__(self, cfg: MellumConfig):
        super().__init__()
        n, h, f = cfg.num_experts, cfg.hidden_size, \
            cfg.moe_intermediate_size
        attr = lambda: nn.ParamAttr(  # noqa: E731
            initializer=nn.initializer.Normal(0.0, cfg.initializer_range))
        self.gate_proj = self.create_parameter((n, h, f), attr=attr())
        self.up_proj = self.create_parameter((n, h, f), attr=attr())
        self.down_proj = self.create_parameter((n, f, h), attr=attr())


class MellumMoE(nn.Layer):
    def __init__(self, cfg: MellumConfig):
        super().__init__()
        self.cfg = cfg
        self.gate = _Weight((cfg.hidden_size, cfg.num_experts),
                            cfg.initializer_range)
        self.experts = MellumExperts(cfg)

    def forward(self, y, valid=None):
        """(out [b, s, hidden], counters int32 [4]): the whole layer, no
        token dropped, no capacity."""
        c = self.cfg
        b, s, h = y.shape
        yt = y.reshape(b * s, h)
        with jax.named_scope("route"):
            w, idx = dm.route_softmax_topk(
                yt, self.gate.w, c.num_experts_per_tok, c.norm_topk_prob)
        e = self.experts
        out, counters = dm.dropless_experts(
            yt, w, idx, e.gate_proj._value, e.up_proj._value,
            e.down_proj._value, (0, c.num_experts),
            None if valid is None else valid.reshape(b * s))
        return out.reshape(b, s, h), counters


class MellumDecoderLayer(nn.Layer):
    def __init__(self, cfg: MellumConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.kind = cfg.layer_types[index]
        self.input_layernorm = _Weight((cfg.hidden_size,), None)
        self.self_attn = MellumAttention(cfg, self.kind)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), None)
        self.mlp = MellumMoE(cfg)

    def forward(self, x, ropes, cache=None):
        eps = self.cfg.rms_norm_eps
        scope = "window" if self.kind == WINDOW else "full"
        with jax.named_scope("block/attn/" + scope):
            a, new_cache = self.self_attn(
                _rms_norm(x, self.input_layernorm.w, eps), ropes[self.kind],
                cache)
            x = x + a
        with jax.named_scope("block/moe"):
            y = _rms_norm(x, self.post_attention_layernorm.w, eps)
            m, counters = self.mlp(
                y, None if cache is None else cache["valid"])
        if new_cache is not None:
            new_cache["counters"] = counters
        return x + m, new_cache


class MellumModel(nn.Layer):
    def __init__(self, cfg: MellumConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                    cfg.initializer_range)
        self.layers = nn.LayerList(
            [MellumDecoderLayer(cfg, i)
             for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), None)

    def forward(self, ids, caches=None):
        b, s = ids.shape
        steps = jnp.arange(s, dtype=jnp.int32)[None, :]
        if caches is None:
            positions = jnp.broadcast_to(steps, (b, s))
        else:
            # every slot is at its own length; the clip keeps a dead
            # slot's garbage inside the positions the model has
            positions = jnp.clip(
                caches[0]["ctx_lens"].astype(jnp.int32)[:, None] + steps,
                0, self.cfg.max_position_embeddings - 1)
        with jax.named_scope("rope_tables"):
            ropes = rotary_tables(positions, self.cfg)
        with jax.named_scope("embed"):
            x = self.embed_tokens.w[ids]
        new_caches = []
        for i, layer in enumerate(self.layers):
            x, nc = layer(x, ropes, None if caches is None else caches[i])
            new_caches.append(nc)
        if caches is not None and caches[0].get("head_at") is not None:
            # the engine reads one position a row: the norm and the head
            # run over that row alone
            at = caches[0]["head_at"].astype(jnp.int32)
            x = jnp.take_along_axis(x, at[:, None, None], axis=1)
        with jax.named_scope("final_norm"):
            x = _rms_norm(x, self.norm.w, self.cfg.rms_norm_eps)
        return x, (None if caches is None else new_caches)


class MellumForCausalLM(nn.Layer):
    def __init__(self, cfg: MellumConfig):
        super().__init__()
        self.cfg = cfg
        self.model = MellumModel(cfg)
        self.lm_head = _Weight((cfg.hidden_size, cfg.vocab_size),
                               cfg.initializer_range)

    def forward(self, input_ids, caches=None):
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        h, new_caches = self.model(ids.astype(jnp.int32), caches)
        with jax.named_scope("lm_head"):
            # float32: a greedy choice between near-equal logits is made
            # on what the accumulator held, not on its rounding
            logits = Tensor(jnp.einsum("bsh,hv->bsv", h, self.lm_head.w,
                                       preferred_element_type=jnp.float32))
        return logits if caches is None else (logits, new_caches)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def _gqa_eligible(self, pages_per_seq: int, page_size: int,
                      num_query_tokens: int = 1) -> bool:
        from ..kernels.paged_attention import _gqa_dispatch

        c = self.cfg
        return _gqa_dispatch(
            c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            page_size, pages_per_seq, num_query_tokens,
            self.model.embed_tokens.w.dtype.itemsize, True)[0]

    def decode_kernel_eligible(self, pages_per_seq: int, page_size: int,
                               quantized: bool = False) -> bool:
        """Whether a decode step's attention reaches the grouped-head
        decode kernel (``paged_decode.gqa_kernel_eligible``, the one
        gate; a window changes no shape the gate reads)."""
        return not quantized and self._gqa_eligible(pages_per_seq, page_size)

    # ------------------------------------------------ the serving contract
    def paged_cache_spec(self, kv_dtype: str = "float32",
                         tensor_parallel: int = 1,
                         speculative: bool = False):
        """What this model keeps, for ``ServingEngine``: in every layer
        ``k_pool`` and ``v_pool`` of ``kv_heads x head_dim`` values a token
        (flat: whole lane rows) in the weights' dtype, in two page groups:
        ``full`` (the ``full_attention`` layers: a context's every page)
        and ``window`` (the ``sliding_attention`` layers: a page is freed
        once it lies behind the window of its slot's next query). Its head
        is computed at the positions the engine names. Refuses, with the
        reason, what it cannot do yet."""
        from ..kernels import flash_attention as fa
        from ..kernels.paged_attention import grouped_pages_staged_fn
        from ..serving.kv_cache import CacheLeaf, PagedCacheSpec, PageGroup

        c = self.cfg
        if tensor_parallel > 1:
            raise ValueError(
                "mellum: tensor_parallel > 1 is not supported: page groups "
                "have no placement under serving/tp.py, which places GPT's "
                "leaves by name")
        if kv_dtype != "float32":
            raise ValueError(
                f"mellum: kv_dtype={kv_dtype!r} is not supported: the int8 "
                "pool's write and gather are GPT's, a page a head (the "
                "pools take the weights' dtype)")
        if speculative:
            raise ValueError(
                "mellum: speculative decoding (spec=) is not supported: "
                "the verify step's K+1 tokens a slot have no windowed "
                "kernel path, and a rejected token's page may already have "
                "pushed one out behind the window")
        dtype = self.model.embed_tokens.w.dtype
        leaves = (CacheLeaf("k_pool", (c.kv_width,), dtype),
                  CacheLeaf("v_pool", (c.kv_width,), dtype))
        by_kind = {kind: tuple(i for i, t in enumerate(c.layer_types)
                               if t == kind) for kind in (FULL, WINDOW)}
        n_full, n_win = len(by_kind[FULL]), len(by_kind[WINDOW])
        w = c.sliding_window
        g = c.num_attention_heads // c.num_key_value_heads

        def pages_staged(num_query_tokens, pages_per_seq, page_size):
            # every layer's worth, by kind and by the path the call takes
            def over_pool(window):
                return grouped_pages_staged_fn(
                    c.num_attention_heads, c.num_key_value_heads,
                    c.head_dim, page_size, pages_per_seq, num_query_tokens,
                    itemsize=dtype.itemsize, window=window)

            full, win = over_pool(None), over_pool(w)
            pool = lambda ctx: n_full * full(ctx) + n_win * win(ctx)  # noqa: E731
            s = num_query_tokens
            if s == 1 or not _flash_prefill(s, c.head_dim)[0]:
                return pool
            # from position 0 the flash forward reads the prompt's own
            # keys: a live (q block, kv block) step fetches a block of ONE
            # KV head for one query head, a page's share of 1 / kv_heads
            per_step = g * fa.grouped_edge(s) // page_size
            flash = per_step * (n_full * fa.grouped_live_steps(s)
                                + n_win * fa.grouped_live_steps(s, w))
            return lambda ctx: np.where(np.asarray(ctx) == 0, flash,
                                        pool(ctx))

        def pages_live(num_query_tokens, page_size):
            def live(ctx, tokens):
                ctx = np.asarray(ctx, np.int64)
                last = (ctx + tokens - 1) // page_size
                # a window layer: from the page of the first position the
                # first new query sees
                first = np.maximum(ctx - w + 1, 0) // page_size
                return n_full * (last + 1) + n_win * (last - first + 1)
            return live

        return PagedCacheSpec(
            num_layers=c.num_hidden_layers,
            max_seq_len=c.max_position_embeddings, dtype=dtype,
            leaves=leaves, counters=MOE_COUNTERS,
            groups=(PageGroup("full", by_kind[FULL]),
                    PageGroup("window", by_kind[WINDOW], window=w)),
            pages_staged=pages_staged, pages_live=pages_live,
            head_at_positions=True,
            no_prefix_sharing=(
                "mellum: a window layer's page of a shared prefix is freed "
                "behind the window of whoever holds it, and the prefix "
                "index names one page a block"))
