"""Ouro (``model_type`` ``ouro``; ``ByteDance/Ouro-2.6B``, the LoopLM family):
a decoder whose whole stack of layers runs ``total_ut_steps`` times for
every token with the same weights. Each layer is full multi-head attention
with rotary positions and a gated SiLU MLP, each sub-layer with an RMSNorm
before it and one after it (the "sandwich"); a final RMSNorm closes every
loop and feeds the next; an exit gate gives each loop a probability of
stopping; the head is untied, with float32 logits.
``tests/refs/ouro_reference.py`` has the equations and what the config
leaves open.

Serving only: ``forward(ids)`` is the whole pass (the exit probabilities on
request), and ``forward(ids, caches=...)`` the paged-cache contract that
``serving.ServingEngine`` calls (see ``paged_cache_spec``). The loops are
ONE loop in the program (``lax.fori_loop``): its body holds every layer
and its attention kernel once, and carries the hidden state and every
layer's pools. In each layer, each loop writes its own keys and values,
computed from that loop's input, and loop ``t`` at a later position
attends to loop ``t``'s: a token keeps ``total_ut_steps x layers`` rows of
keys and values. They live side by side in ONE page group: a layer's
``k_pool`` / ``v_pool`` row is ``total_ut_steps x kv_heads x head_dim``
wide and loop ``t`` owns columns ``[t w, (t + 1) w)`` (``w = kv_heads x
head_dim``), so all loops of a token are written, shared by prefix and
freed together under one table and one lifetime. A decode step runs
``gqa_decode_attention`` with the loop as a scalar operand of its page
copies (the instance ``gqa_decode_attention_loop``); a prefill from
position 0 the grouped flash forward over the loop's own q, k, v; behind
cached tokens (a chunk's tail, a prefix hit) the composite over loop
``t``'s columns of the pool.

At ``early_exit_threshold`` 1 every token runs every loop and its logits
are the last loop's, whatever the gate says: the serving path does not
compute the gate, which is exact, not a matter of tolerance.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from .mellum import _flash_prefill, _rms_norm, _rotate, _Weight, \
    _whole_attention

__all__ = ["OuroConfig", "OuroForCausalLM", "rotary_table"]

#: the pool leaves a layer keeps, the loop's carry
POOL_KEYS = ("k_pool", "v_pool")


@dataclass
class OuroConfig:
    """The model's ``config.json``, key for key; the defaults are
    Ouro-2.6B's published values."""
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    max_position_embeddings: int = 65536
    rope_theta: float = 1000000.0
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    use_sliding_window: bool = False
    sliding_window: int | None = None
    max_window_layers: int = 48
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads do not group over the KV heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary pairs)")
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {self.total_ut_steps} < 1")
        if not 0 < self.early_exit_threshold <= 1:
            raise ValueError("early_exit_threshold lies in (0, 1]")
        if self.tie_word_embeddings or self.hidden_act != "silu" \
                or self.rope_scaling is not None:
            raise ValueError("only the untied head, silu and unscaled "
                             "rotary are here (as published)")
        if self.use_sliding_window or self.sliding_window is not None:
            raise ValueError("no layer has a window here (as published)")

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


def rotary_table(positions, cfg: OuroConfig):
    """``(cos, sin)``, float32 ``[b, s, head_dim]``: rotate-half pairs at
    ``inv_freq_i = theta^(-2i/d)``, no scaling."""
    d = cfg.head_dim
    inv = float(cfg.rope_theta) ** (-np.arange(0, d, 2, dtype=np.float64)
                                    / d)
    ang = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv, jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _heads_of(y, w, d: int):
    """``y @ w`` as heads ``[b, s, out / d, d]``, for the query and key
    projections (``w`` ``[in, out]``, as published). The weight is the
    product's left operand: inside the step programs' loop the TPU
    compiler otherwise takes it with ``in`` minor and copies it into that
    order before the loop, every call (at Ouro-2.6B 96 copies of 8 MB a
    decode step, about 3 ms of 38 on a TPU v5e); a program without a loop
    fuses that order into the product. The same products, summed over
    ``in``."""
    b, s, _ = y.shape
    heads = jax.lax.dot_general(w, y, (((0,), (2,)), ((), ())))  # [o,b,s]
    return jnp.moveaxis(heads, 0, -1).reshape(b, s, -1, d)


# ------------------------------------------------------------------ layers
class OuroAttention(nn.Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.cfg = cfg
        h, d, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        self.q_proj = _Weight((h, cfg.num_attention_heads * d), std)
        self.k_proj = _Weight((h, cfg.kv_width), std)
        self.v_proj = _Weight((h, cfg.kv_width), std)
        self.o_proj = _Weight((cfg.num_attention_heads * d, h), std)

    def forward(self, y, rope, cache=None, loop=None):
        """``loop``: the loop this call is of (a traced int32 scalar), whose
        columns of the pools it writes and reads."""
        from ..kernels import paged_attention as pa

        c = self.cfg
        b, s, _ = y.shape
        d, scale = c.head_dim, c.head_dim ** -0.5
        q = _heads_of(y, self.q_proj.w, d)
        k = _heads_of(y, self.k_proj.w, d)
        v = jnp.matmul(y, self.v_proj.w).reshape(b, s, -1, d)
        with jax.named_scope("rope"):
            q, k = _rotate(q, *rope), _rotate(k, *rope)
        q = q.transpose(0, 2, 1, 3)                           # [b,H,s,d]
        if cache is None:
            o = _whole_attention(q, k, v, scale, None)
            new_cache = None
        else:
            k_pool, v_pool = cache["k_pool"], cache["v_pool"]
            ctx = cache["ctx_lens"].astype(jnp.int32)
            table, valid = cache["page_table"], cache["valid"]
            with jax.named_scope("kv_write"):
                # this loop's columns of each token's row
                k_pool, v_pool = pa.paged_write_loop(
                    k_pool, v_pool, k.reshape(b, s, -1),
                    v.reshape(b, s, -1), table, ctx, valid, loop)

            def over_pool():
                # a decode step: the grouped-head kernel over this loop's
                # columns; several tokens a row: the composite
                return pa.paged_attention(q, k_pool, v_pool, table, ctx,
                                          scale=scale, loop=loop,
                                          loops=c.total_ut_steps)

            flash, interpret = (False, False) if s == 1 \
                else _flash_prefill(s, d)
            if flash:
                from ..kernels.flash_attention import flash_fwd_grouped

                # from position 0 every key a query sees is in this call:
                # the loop's own q, k, v, no pool read
                o = jax.lax.cond(
                    jnp.all(ctx == 0),
                    lambda: flash_fwd_grouped(
                        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                        scale, None, interpret),
                    over_pool)
            else:
                o = over_pool()
            new_cache = {"k_pool": k_pool, "v_pool": v_pool}
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1).astype(y.dtype)
        return jnp.matmul(o, self.o_proj.w), new_cache


class OuroMLP(nn.Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        h, f, std = cfg.hidden_size, cfg.intermediate_size, \
            cfg.initializer_range
        self.gate_proj = _Weight((h, f), std)
        self.up_proj = _Weight((h, f), std)
        self.down_proj = _Weight((f, h), std)

    def forward(self, m):
        return jnp.matmul(jax.nn.silu(jnp.matmul(m, self.gate_proj.w))
                          * jnp.matmul(m, self.up_proj.w), self.down_proj.w)


class OuroDecoderLayer(nn.Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        self.input_layernorm = _Weight((h,), None)
        self.self_attn = OuroAttention(cfg)
        self.input_layernorm_2 = _Weight((h,), None)
        self.post_attention_layernorm = _Weight((h,), None)
        self.mlp = OuroMLP(cfg)
        self.post_attention_layernorm_2 = _Weight((h,), None)

    def forward(self, x, rope, cache=None, loop=None):
        eps = self.cfg.rms_norm_eps
        with jax.named_scope("block/attn"):
            a, new_cache = self.self_attn(
                _rms_norm(x, self.input_layernorm.w, eps), rope, cache, loop)
            x = x + _rms_norm(a, self.input_layernorm_2.w, eps)
        with jax.named_scope("block/mlp"):
            f = self.mlp(_rms_norm(x, self.post_attention_layernorm.w, eps))
            x = x + _rms_norm(f, self.post_attention_layernorm_2.w, eps)
        return x, new_cache


class OuroExitGate(nn.Layer):
    """``lambda = sigmoid(h . w + b)``, one linear map with a bias."""

    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.weight = self.create_parameter(
            (cfg.hidden_size, 1), attr=nn.ParamAttr(
                initializer=nn.initializer.Normal(0.0,
                                                  cfg.initializer_range)))
        self.bias = self.create_parameter(
            (1,), attr=nn.ParamAttr(initializer=nn.initializer.Constant(0.0)))

    def forward(self, h):
        z = jnp.einsum("bsh,ho->bso", h, self.weight._value,
                       preferred_element_type=jnp.float32)[..., 0]
        return jax.nn.sigmoid(z + self.bias._value.astype(jnp.float32)[0])


def exit_probabilities(lambdas):
    """``[loops, ...]`` gate values to the exit distribution over the loops:
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the last loop the rest."""
    stay = jnp.cumprod(1.0 - lambdas, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return (lambdas * before).at[-1].set(before[-1])


class OuroModel(nn.Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                    cfg.initializer_range)
        self.layers = nn.LayerList(
            [OuroDecoderLayer(cfg) for _ in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), None)
        self.early_exit_gate = OuroExitGate(cfg)

    def _loop_norm(self, h):
        with jax.named_scope("loop_norm"):
            return _rms_norm(h, self.norm.w, self.cfg.rms_norm_eps)

    def forward(self, ids, caches=None, with_exits: bool = False):
        """(the last loop's normed state, new caches or None, the exit
        probabilities ``[loops, b, s]`` where asked for)."""
        c = self.cfg
        b, s = ids.shape
        steps = jnp.arange(s, dtype=jnp.int32)[None, :]
        if caches is None:
            positions = jnp.broadcast_to(steps, (b, s))
        else:
            # every slot is at its own length; the clip keeps a dead
            # slot's garbage inside the positions the model has
            positions = jnp.clip(
                caches[0]["ctx_lens"].astype(jnp.int32)[:, None] + steps,
                0, c.max_position_embeddings - 1)
        with jax.named_scope("rope_tables"):
            rope = rotary_table(positions, c)
        with jax.named_scope("embed"):
            x = self.embed_tokens.w[ids]
        if caches is None:
            def whole(h, _):
                with jax.named_scope("loop"):
                    for layer in self.layers:
                        h, _ = layer(h, rope)
                    h = self._loop_norm(h)
                with jax.named_scope("exit_gate"):
                    lam = self.early_exit_gate(h)
                return h, lam

            h, lambdas = jax.lax.scan(whole, x, None,
                                      length=c.total_ut_steps)
            return h, None, (exit_probabilities(lambdas) if with_exits
                             else None)
        # the pools are the loop's carry; the rest of each layer's cache
        # (tables, lengths) is the same in every loop
        shared = [{k: v for k, v in cache.items() if k not in POOL_KEYS}
                  for cache in caches]
        pools = [{k: cache[k] for k in POOL_KEYS} for cache in caches]

        def one_loop(t, carry):
            h, pools = carry
            new = []
            with jax.named_scope("loop"):
                for layer, rest, pool in zip(self.layers, shared, pools):
                    h, nc = layer(h, rope, dict(rest, **pool), t)
                    new.append(nc)
                h = self._loop_norm(h)
            return h, new

        h, pools = jax.lax.fori_loop(0, c.total_ut_steps, one_loop,
                                     (x, pools))
        if caches[0].get("head_at") is not None:
            # the engine reads one position a row: the head runs over that
            # row alone
            at = caches[0]["head_at"].astype(jnp.int32)
            h = jnp.take_along_axis(h, at[:, None, None], axis=1)
        return h, [dict(rest, **pool) for rest, pool in zip(shared, pools)], \
            None


class OuroForCausalLM(nn.Layer):
    def __init__(self, cfg: OuroConfig):
        super().__init__()
        self.cfg = cfg
        self.model = OuroModel(cfg)
        self.lm_head = _Weight((cfg.hidden_size, cfg.vocab_size),
                               cfg.initializer_range)

    def forward(self, input_ids, caches=None, with_exits: bool = False):
        """Logits ``[b, s, vocab]`` float32 (the whole pass; with
        ``with_exits`` also the exit probabilities ``[b, loops, s]``), or
        ``(logits, new_caches)`` through the paged cache."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        h, new_caches, exits = self.model(ids.astype(jnp.int32), caches,
                                          with_exits)
        with jax.named_scope("lm_head"):
            # float32: a greedy choice between near-equal logits is made
            # on what the accumulator held, not on its rounding
            logits = Tensor(jnp.einsum("bsh,hv->bsv", h, self.lm_head.w,
                                       preferred_element_type=jnp.float32))
        if caches is not None:
            return logits, new_caches
        if with_exits:
            return logits, Tensor(jnp.moveaxis(exits, 0, 1))
        return logits

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def decode_kernel_eligible(self, pages_per_seq: int, page_size: int,
                               quantized: bool = False) -> bool:
        """Whether a decode step's attention reaches the grouped-head
        decode kernel (``paged_decode.gqa_kernel_eligible``, the one gate;
        the loop's columns change no shape the gate reads)."""
        from ..kernels.paged_attention import _gqa_dispatch

        c = self.cfg
        return not quantized and _gqa_dispatch(
            c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            page_size, pages_per_seq, 1,
            self.model.embed_tokens.w.dtype.itemsize, True)[0]

    # ------------------------------------------------ the serving contract
    def paged_cache_spec(self, kv_dtype: str = "float32",
                         tensor_parallel: int = 1,
                         speculative: bool = False):
        """What this model keeps, for ``ServingEngine``: in every layer
        ``k_pool`` and ``v_pool`` of ``total_ut_steps x kv_heads x
        head_dim`` values a token (flat: loop ``t``'s keys in columns ``[t
        w, (t + 1) w)``) in the weights' dtype, one page group. Its head is
        computed at the positions the engine names. The pages staged and
        live are counted over every (loop, layer). Refuses, with the
        reason, what it cannot do yet."""
        from ..kernels import flash_attention as fa
        from ..kernels.paged_attention import grouped_pages_staged_fn
        from ..serving.kv_cache import CacheLeaf, PagedCacheSpec

        c = self.cfg
        n = c.total_ut_steps
        if c.early_exit_threshold < 1:
            raise ValueError(
                f"ouro: early_exit_threshold {c.early_exit_threshold} < 1 "
                "is not supported: a token that leaves early has no keys "
                "in the later loops, which later tokens' loops attend to")
        if tensor_parallel > 1:
            raise ValueError(
                "ouro: tensor_parallel > 1 is not supported: serving/tp.py "
                "places GPT's leaves by name and has no placement of a "
                "pool row that holds every loop's heads side by side")
        if kv_dtype != "float32":
            raise ValueError(
                f"ouro: kv_dtype={kv_dtype!r} is not supported: the int8 "
                "pool's write and gather are GPT's, a page a head (the "
                "pools take the weights' dtype)")
        if speculative:
            raise ValueError(
                "ouro: speculative decoding (spec=) is not supported: the "
                "verify step's K+1 tokens a slot have no looped kernel "
                "path")
        dtype = self.model.embed_tokens.w.dtype
        width = n * c.kv_width
        leaves = (CacheLeaf("k_pool", (width,), dtype),
                  CacheLeaf("v_pool", (width,), dtype))
        passes = n * c.num_hidden_layers
        g = c.num_attention_heads // c.num_key_value_heads

        def pages_staged(num_query_tokens, pages_per_seq, page_size):
            # a (loop, layer)'s worth of pages, times every pass
            pool = grouped_pages_staged_fn(
                c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                page_size, pages_per_seq, num_query_tokens,
                itemsize=dtype.itemsize)
            s = num_query_tokens
            if s == 1 or not _flash_prefill(s, c.head_dim)[0]:
                return lambda ctx: passes * pool(ctx)
            # from position 0 the flash forward reads the loop's own keys:
            # a live (q block, kv block) step fetches a block of ONE KV
            # head for one query head, a page's share of 1 / kv_heads
            flash = passes * g * fa.grouped_edge(s) // page_size \
                * fa.grouped_live_steps(s)
            return lambda ctx: np.where(np.asarray(ctx) == 0, flash,
                                        passes * pool(ctx))

        def pages_live(num_query_tokens, page_size):
            def live(ctx, tokens):
                last = (np.asarray(ctx, np.int64) + tokens - 1) // page_size
                return passes * (last + 1)
            return live

        return PagedCacheSpec(
            num_layers=c.num_hidden_layers,
            max_seq_len=c.max_position_embeddings, dtype=dtype,
            leaves=leaves, pages_staged=pages_staged, pages_live=pages_live,
            head_at_positions=True,
            no_wire=(
                "ouro: the wire's page frame carries a pool of a heads axis "
                "a token, and a page here holds every loop's keys in one "
                "flat row"))
