"""Brumby (``model_type`` ``brumby``; ``manifestai/Brumby-14B-Base``): the
Qwen3 block with its attention replaced in every layer by gated power
retention of degree 2 (Gelada, Buckman, Zhang et al., *Scaling Context
Requires Rethinking Attention*, arXiv:2507.04239; manifestai's
``retention`` library). A KV group ``g`` (its ``group`` query heads share
one key, value and gate) at token ``t``:

    q_h = RoPE(RMSNorm_q(W_q y)_h)   k = RoPE(RMSNorm_k(W_k y)_g)
    v = (W_v y)_g                    log gamma = logsigmoid(w_g . y + b_g)
    S_t = gamma_t S_{t-1} + phi(k_t) v_t^T     z_t = gamma_t z_{t-1} + phi(k_t)
    o_h = phi(q_h)^T S_t / (phi(q_h)^T z_t + eps)

with ``phi`` the symmetric degree-2 expansion (``phi(a) . phi(b) = (a .
b)^2``, ``kernels/power_retention.py``) and ``y`` the block's normed input;
then ``W_o``, the residual and the gated SiLU MLP, a final RMSNorm and an
untied head with float32 logits. The config has no key for the retention
layer: ``tests/refs/brumby_reference.py`` lists what is assumed (the
degree, a gate a KV head, QK-norm and rotary kept, the normaliser ``z``).

Serving only: ``forward(ids)`` is the whole pass from zero states, and
``forward(ids, caches=...)`` the cache contract that
``serving.ServingEngine`` calls (see ``paged_cache_spec``). A layer keeps
``state`` ``[groups, D, head_dim]`` and ``z`` ``[groups, tiles,
head_dim]`` float32 a SLOT, whatever the context's length, and nothing a
token: the model has no page. A call of several tokens a row (a prefill, a
chunk) runs the chunked form as composite XLA from the slot's state (from
zeros at position 0): inside a chunk the ``(q . k)^2`` weights under the
gate's decay, across chunks the carried ``S`` and ``z``
(:func:`retention_chunked`).
A call of one token a slot (decode) is the recurrence, one Pallas kernel
(``kernels/power_retention.py``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..kernels import power_retention as pr
from .mellum import _rms_norm, _rotate, _Weight
from .ouro import OuroMLP, _heads_of, rotary_table

__all__ = ["BrumbyConfig", "BrumbyForCausalLM", "RETENTION_COUNTERS",
           "retention_chunked", "retention_sequential"]

#: the step counters a cache call reports (``new_cache["counters"]`` of a
#: layer, int32 [2]), under the names the engine publishes them: slots
#: whose state the launch advanced, and slot rows the update read and wrote
RETENTION_COUNTERS = ("retention_state_rows_live_total",
                      "retention_state_rows_moved_total")

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass
class BrumbyConfig:
    """The model's ``config.json``, key for key; the defaults are
    Brumby-14B-Base's published values. ``power`` and ``chunk_size`` are
    not in the config: the degree of the retention (2, the paper's) and
    the prefill's chunk."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 32768
    rope_theta: float = 1000000.0
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    attention_bias: bool = False
    use_sliding_window: bool = False
    sliding_window: int | None = None
    max_window_layers: int = 40
    power: int = 2
    chunk_size: int = 128
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads do not group over the KV heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary pairs)")
        if self.power != 2:
            raise ValueError("only power retention of degree 2 is here")
        if self.tie_word_embeddings or self.hidden_act != "silu" \
                or self.rope_scaling is not None or self.attention_bias:
            raise ValueError("only the untied head, silu, unscaled rotary "
                             "and no projection bias are here (as "
                             "published)")
        if self.use_sliding_window or self.sliding_window is not None:
            raise ValueError("no layer has a window here (as published)")
        if self.chunk_size < 1:
            raise ValueError(f"chunk_size {self.chunk_size} < 1")

    # what the serving engine reads of any model's config
    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def group(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def feature_dim(self) -> int:
        return pr.feature_dim(self.head_dim)


# ---------------------------------------------------------- the two forms
def retention_sequential(q, k, v, log_g, s0, z0):
    """The recurrence token by token (``lax.scan``), float32: q ``[b, s,
    groups, group, d]``, k and v ``[b, s, groups, d]``, log_g ``[b, s,
    groups]`` (0 and a zero key where a position is padding: the state
    then stays), s0 ``[b, groups, D, d]``, z0 ``[b, groups, D]`` -> (o
    ``[b, s, groups, group, d]``, final state, final z)."""
    def step(carry, inp):
        state, z = carry
        q_t, k_t, v_t, g_t = inp
        pk = pr.phi(k_t)
        gamma = jnp.exp(g_t)
        state = gamma[..., None, None] * state + pk[..., None] \
            * v_t[:, :, None, :]
        z = gamma[..., None] * z + pk
        pq = pr.phi(q_t)
        num = jnp.einsum("bghD,bgDd->bghd", pq, state, precision=_HIGHEST)
        den = jnp.einsum("bghD,bgD->bgh", pq, z, precision=_HIGHEST)
        return (state, z), num / (den[..., None] + pr.EPS)

    seq = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # noqa: E731
    (state, z), o = jax.lax.scan(
        step, (s0.astype(jnp.float32), z0.astype(jnp.float32)),
        (seq(q), seq(k), seq(v), seq(log_g)))
    return jnp.moveaxis(o, 0, 1), state, z


def retention_chunked(q, k, v, log_g, s0, z0, chunk: int):
    """The same recurrence in chunks of ``chunk`` tokens, shapes and dtype
    as :func:`retention_sequential`. Inside a chunk, token ``t`` reads
    token ``u <= t`` through ``(q_t . k_u)^2 exp(L_t - L_u)`` (``L`` the
    chunk's running sum of ``log gamma``); the state the chunk started
    from adds its readout decayed by ``exp(L_t)``; a short scan carries
    ``S`` and ``z`` from chunk to chunk, so that ``phi`` of a chunk's
    queries and keys is the largest thing that stands at once."""
    f32 = jnp.float32
    b, s, groups, group, d = q.shape
    c = min(chunk, s)
    pad = -s % c
    if pad:
        q, k, v, log_g = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, log_g))
    nc = (s + pad) // c
    by_chunk = lambda a: jnp.moveaxis(  # noqa: E731
        a.astype(f32).reshape((b, nc, c) + a.shape[2:]), 1, 0)
    seen = jnp.tril(jnp.ones((c, c), bool))
    ein = functools.partial(jnp.einsum, precision=_HIGHEST)

    def one_chunk(carry, inp):
        state, z = carry
        qc, kc, vc, gc = inp                     # [b, c, ...]
        cum = jnp.cumsum(gc, axis=1)             # [b, c, groups]
        with jax.named_scope("chunk"):
            qk = ein("btghd,bugd->bghtu", qc, kc)
            gap = cum.transpose(0, 2, 1)[:, :, None, :, None] \
                - cum.transpose(0, 2, 1)[:, :, None, None, :]
            w = jnp.where(seen, jnp.square(qk)
                          * jnp.exp(jnp.where(seen, gap, 0.0)), 0.0)
            num = ein("bghtu,bugd->btghd", w, vc)
            den = jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)   # [b,t,g,h]
        with jax.named_scope("carry"):
            decay = jnp.exp(cum)[..., None]      # [b, c, groups, 1]
            pq = pr.phi(qc)                      # [b, c, groups, group, D]
            num = num + decay[..., None] * ein("btghD,bgDd->btghd", pq,
                                               state)
            den = den + decay * ein("btghD,bgD->btgh", pq, z)
            # what the chunk adds to its end state, and its whole decay
            to_end = jnp.exp(cum[:, -1:] - cum)  # [b, c, groups]
            pk = pr.phi(kc) * to_end[..., None]
            whole = jnp.exp(cum[:, -1])          # [b, groups]
            state = whole[..., None, None] * state \
                + ein("bugD,bugd->bgDd", pk, vc)
            z = whole[..., None] * z + jnp.sum(pk, axis=1)
        return (state, z), num / (den[..., None] + pr.EPS)

    (state, z), o = jax.lax.scan(
        one_chunk, (s0.astype(f32), z0.astype(f32)),
        (by_chunk(q), by_chunk(k), by_chunk(v), by_chunk(log_g)))
    o = jnp.moveaxis(o, 0, 1).reshape((b, nc * c, groups, group, d))
    return o[:, :s], state, z


# ------------------------------------------------------------------ layers
class _Gate(nn.Layer):
    """``log gamma = logsigmoid(y w + b)``: a gate a KV head."""

    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        self.weight = self.create_parameter(
            (cfg.hidden_size, cfg.num_key_value_heads), attr=nn.ParamAttr(
                initializer=nn.initializer.Normal(0.0,
                                                  cfg.initializer_range)))
        self.bias = self.create_parameter(
            (cfg.num_key_value_heads,), attr=nn.ParamAttr(
                initializer=nn.initializer.Constant(0.0)))

    def forward(self, y):
        a = jnp.einsum("bsh,hg->bsg", y, self.weight._value,
                       preferred_element_type=jnp.float32)
        return jax.nn.log_sigmoid(a + self.bias._value.astype(jnp.float32))


class BrumbyRetention(nn.Layer):
    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        self.cfg = cfg
        h, d, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        kv = cfg.num_key_value_heads * d
        self.q_proj = _Weight((h, cfg.num_attention_heads * d), std)
        self.k_proj = _Weight((h, kv), std)
        self.v_proj = _Weight((h, kv), std)
        self.o_proj = _Weight((cfg.num_attention_heads * d, h), std)
        self.q_norm = _Weight((d,), None)
        self.k_norm = _Weight((d,), None)
        self.gate = _Gate(cfg)

    def forward(self, y, rope, cache=None):
        """y [b, s, hidden] after the block's first norm -> (out, the
        layer's new cache or None)."""
        c = self.cfg
        b, s, _ = y.shape
        d, groups, eps = c.head_dim, c.num_key_value_heads, c.rms_norm_eps
        with jax.named_scope("qkv"):
            q = _heads_of(y, self.q_proj.w, d)
            k = _heads_of(y, self.k_proj.w, d)
            v = jnp.matmul(y, self.v_proj.w).reshape(b, s, groups, d)
            q = _rms_norm(q, self.q_norm.w, eps)
            k = _rms_norm(k, self.k_norm.w, eps)
        with jax.named_scope("gate"):
            log_g = self.gate(y)                          # [b, s, groups]
        with jax.named_scope("rope"):
            q, k = _rotate(q, *rope), _rotate(k, *rope)
        q = q.reshape(b, s, groups, c.group, d).astype(jnp.float32)
        k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        new_cache = None
        if cache is None:
            f32 = jnp.float32
            o, _, _ = retention_chunked(
                q, k, v, log_g, jnp.zeros((b, groups, c.feature_dim, d), f32),
                jnp.zeros((b, groups, c.feature_dim), f32), c.chunk_size)
        else:
            valid, slots = cache["valid"], cache.get("slots")
            pool, zpool = cache["state"], cache["z"]
            if slots is None:    # row i is slot i: a decode step
                if s != 1:
                    raise NotImplementedError(
                        "brumby: several tokens a slot for every slot at "
                        "once (a verify step) has no state path")
                live = valid[:, 0]
                with jax.named_scope("state"):
                    state, zn, o, moved = pr.retention_update(
                        pool, zpool, k[:, 0], q[:, 0], v[:, 0], log_g[:, 0],
                        live)
                o = o[:, None]
                new_cache = dict(cache, state=state, z=zn,
                                 counters=jnp.stack(
                                     [jnp.sum(live, dtype=jnp.int32),
                                      moved]))
            else:
                # a padded position neither decays the state nor adds to
                # it; a request that starts at position 0 starts from
                # zeros, whatever the slot's last owner left
                keep = valid[..., None]
                log_g = jnp.where(keep, log_g, 0.0)
                k = jnp.where(keep[..., None], k, 0.0)
                v = jnp.where(keep[..., None], v, 0.0)
                fresh = (cache["ctx_lens"] == 0)[:, None, None]
                s0 = jnp.where(fresh[..., None], 0.0, pool[slots])
                z0 = jnp.where(fresh, 0.0, pr.z_flat(zpool[slots], d))
                o, s1, z1 = retention_chunked(q, k, v, log_g, s0, z0,
                                              c.chunk_size)
                rows = jnp.int32(b)
                new_cache = dict(
                    cache, state=pool.at[slots].set(s1),
                    z=zpool.at[slots].set(pr.z_rows(z1, d)),
                    counters=jnp.stack([rows, rows]))
        o = o.reshape(b, s, -1).astype(y.dtype)
        return jnp.matmul(o, self.o_proj.w), new_cache


class BrumbyDecoderLayer(nn.Layer):
    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = _Weight((cfg.hidden_size,), None)
        self.self_attn = BrumbyRetention(cfg)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), None)
        self.mlp = OuroMLP(cfg)   # the same gated SiLU MLP

    def forward(self, x, rope, cache=None):
        eps = self.cfg.rms_norm_eps
        with jax.named_scope("block/retention"):
            a, new_cache = self.self_attn(
                _rms_norm(x, self.input_layernorm.w, eps), rope, cache)
            x = x + a
        with jax.named_scope("block/mlp"):
            f = self.mlp(_rms_norm(x, self.post_attention_layernorm.w, eps))
        return x + f, new_cache


class BrumbyModel(nn.Layer):
    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                    cfg.initializer_range)
        self.layers = nn.LayerList(
            [BrumbyDecoderLayer(cfg) for _ in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), None)

    def forward(self, ids, caches=None):
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
        new_caches = []
        for i, layer in enumerate(self.layers):
            x, nc = layer(x, rope, None if caches is None else caches[i])
            new_caches.append(nc)
        if caches is not None and caches[0].get("head_at") is not None:
            # the engine reads one position a row: the head runs over that
            # row alone
            at = caches[0]["head_at"].astype(jnp.int32)
            x = jnp.take_along_axis(x, at[:, None, None], axis=1)
        with jax.named_scope("final_norm"):
            x = _rms_norm(x, self.norm.w, c.rms_norm_eps)
        return x, (None if caches is None else new_caches)


class BrumbyForCausalLM(nn.Layer):
    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        self.cfg = cfg
        self.model = BrumbyModel(cfg)
        self.lm_head = _Weight((cfg.hidden_size, cfg.vocab_size),
                               cfg.initializer_range)

    def forward(self, input_ids, caches=None):
        """Logits ``[b, s, vocab]`` float32 (the whole pass), or ``(logits,
        new_caches)`` through the cache."""
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

    def decode_kernel_eligible(self, pages_per_seq: int, page_size: int,
                               quantized: bool = False) -> bool:
        """Whether the decode step's state update reaches its Pallas
        kernel (``power_retention.retention_kernel_eligible``, the one
        gate)."""
        from ..kernels._common import on_tpu_backend
        from ..utils.flags import flag

        return pr.retention_kernel_eligible(
            self.cfg.head_dim, on_tpu=on_tpu_backend(),
            flags_on=bool(flag("FLAGS_use_pallas_kernels", True)),
            interpret=bool(flag("FLAGS_ragged_interpret", False)))[0]

    # ------------------------------------------------ the serving contract
    def paged_cache_spec(self, kv_dtype: str = "float32",
                         tensor_parallel: int = 1,
                         speculative: bool = False):
        """What this model keeps, for ``ServingEngine``: in every layer
        ``state`` ``[groups, D, head_dim]`` and ``z`` ``[groups, tiles,
        head_dim]`` (``D`` lane-dense, padded with zeros to whole lane rows,
        as the decode kernel moves it), float32, a SLOT; no leaf a token
        and so no page at all. Its head
        is computed at the positions the engine names. Refuses, with the
        reason, what a model of recurrent state alone cannot do yet."""
        from ..serving.kv_cache import CacheLeaf, PagedCacheSpec

        c = self.cfg
        if tensor_parallel > 1:
            raise ValueError(
                "brumby: tensor_parallel > 1 is not supported: a slot's "
                "state has no placement under serving/tp.py, which places "
                "GPT's leaves by name")
        if kv_dtype != "float32":
            raise ValueError(
                f"brumby: kv_dtype={kv_dtype!r} is not supported: the model "
                "keeps no keys and values to quantize, and its state is "
                "float32")
        if speculative:
            raise ValueError(
                "brumby: speculative decoding (spec=) is not supported: a "
                "rejected draft token cannot be taken back out of a "
                "recurrent state")
        g = c.num_key_value_heads
        leaves = (CacheLeaf("state", (g, c.feature_dim, c.head_dim),
                            jnp.float32, per_slot=True),
                  CacheLeaf("z", pr.z_shape(g, c.head_dim), jnp.float32,
                            per_slot=True))
        return PagedCacheSpec(
            num_layers=c.num_hidden_layers,
            max_seq_len=c.max_position_embeddings,
            dtype=self.model.embed_tokens.w.dtype, leaves=leaves,
            counters=RETENTION_COUNTERS, head_at_positions=True,
            no_prefix_sharing=(
                "brumby: the model keeps no page to share: a prefix would "
                "need a snapshot of every layer's state after it, and none "
                "is kept"),
            no_wire="brumby: the model keeps no page to send")
