"""Kimi-K2 (``model_type`` ``kimi_k2``): the DeepSeek-V3 decoder layer
(arXiv:2412.19437 section 2.1; MLA from DeepSeek-V2, arXiv:2405.04434
section 2.1). Pre-RMSNorm blocks of multi-head latent attention with a
decoupled rotary key under YaRN frequencies, a gated SiLU MLP in the
leading dense layers and a sigmoid-routed expert layer with a shared
expert in every later one, a final RMSNorm and an untied head.

Serving only: ``forward(ids)`` is the whole expanded pass, and
``forward(ids, caches=...)`` the paged-cache contract that
``serving.ServingEngine`` calls (see ``paged_cache_spec``). The cache is
LATENT: one row a token a layer, ``[c_kv after its norm, k_rope after
RoPE]``, no heads axis. A call of one token a row (decode) runs the
absorbed form over it, a call of more (prefill) the expanded form.

``KimiK2Config.held_experts = (first, count)`` beside ``n_routed_experts``
is a chip's share under expert parallelism: the router keeps its published
width, the layer computes its own experts' part (``dropless_moe.py``).

Left out: the vision tower of the family's siblings (text only), the
training-only keys (``seq_aux``, ``tf_legacy_loss``), multi-token
prediction (``num_nextn_predict_layers`` 0), ``n_group`` / ``topk_group``
other than the published 1.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..incubate.distributed.models import dropless_moe as dm
from ..kernels import latent_paged_attention as lpa

__all__ = ["KimiK2Config", "KimiK2ForCausalLM", "yarn_inv_freq",
           "softmax_scale", "MOE_COUNTERS"]

#: the step counters a paged call reports (``new_cache["counters"]`` of an
#: expert layer, int32 [4]), under the names the engine publishes them
MOE_COUNTERS = tuple(
    f"moe_{n}_total" for n in dm.COUNTERS)


def _yarn():
    return {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


@dataclass
class KimiK2Config:
    """The language model's ``config.json``, key for key; the defaults are
    Kimi-K2.7-Code's published values."""
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    n_routed_experts: int = 384
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.827
    norm_topk_prob: bool = True
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: dict = field(default_factory=_yarn)
    #: (first, count): the routed experts this chip holds; None = all
    held_experts: tuple | None = None
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.held_experts is None:
            self.held_experts = (0, self.n_routed_experts)
        first, count = self.held_experts = tuple(
            int(v) for v in self.held_experts)
        if not (0 <= first and 0 < count
                and first + count <= self.n_routed_experts):
            raise ValueError(
                f"held_experts {self.held_experts} is not a range of the "
                f"{self.n_routed_experts} routed experts")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")

    # what the serving engine reads of any model's config
    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim


# ------------------------------------------------------------- positions
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, rs: dict):
    """The ``dim / 2`` rotary frequencies, float32: ``theta_i`` blended
    with ``theta_i / factor`` by YaRN's linear ramp between the correction
    dimensions of ``beta_fast`` and ``beta_slow``."""
    theta = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rs is None:
        return theta

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return theta / rs["factor"] * ramp + theta * (1.0 - ramp)


def softmax_scale(cfg: KimiK2Config) -> float:
    """``(qk_nope + qk_rope) ** -0.5 * m ** 2``, ``m = 0.1 *
    mscale_all_dim * ln(factor) + 1``."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_scaling is not None:
        m = _yarn_mscale(cfg.rope_scaling["factor"],
                         cfg.rope_scaling["mscale_all_dim"])
        scale *= m * m
    return scale


def _rope(x, positions, cfg: KimiK2Config):
    """Rotate the interleaved pairs ``(x[2i], x[2i+1])`` of the last axis
    in float32. x [b, s, ..., d]; positions [b, s]."""
    rs = cfg.rope_scaling
    ang = positions.astype(jnp.float32)[..., None] \
        * yarn_inv_freq(x.shape[-1], cfg.rope_theta, rs)
    t = 1.0 if rs is None else (
        _yarn_mscale(rs["factor"], rs["mscale"])
        / _yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    cos, sin = jnp.cos(ang) * t, jnp.sin(ang) * t
    while cos.ndim < x.ndim:
        cos, sin = cos[:, :, None], sin[:, :, None]
    x32 = x.astype(jnp.float32)
    x0, x1 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rms_norm(x, w, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _gated_mlp(y, gate, up, down):
    return jnp.matmul(jax.nn.silu(jnp.matmul(y, gate)) * jnp.matmul(y, up),
                      down)


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


class KimiK2Attention(nn.Layer):
    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        self.cfg = cfg
        h, nh, std = cfg.hidden_size, cfg.num_attention_heads, \
            cfg.initializer_range
        dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.q_a_proj = _Weight((h, cfg.q_lora_rank), std)
        self.q_a_layernorm = _Weight((cfg.q_lora_rank,), None)
        self.q_b_proj = _Weight((cfg.q_lora_rank, nh * dq), std)
        self.kv_a_proj_with_mqa = _Weight((h, cfg.latent_width), std)
        self.kv_a_layernorm = _Weight((cfg.kv_lora_rank,), None)
        self.kv_b_proj = _Weight(
            (cfg.kv_lora_rank, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            std)
        self.o_proj = _Weight((nh * cfg.v_head_dim, h), std)

    def _queries(self, y, positions):
        c = self.cfg
        b, s, _ = y.shape
        with jax.named_scope("q_proj"):
            c_q = _rms_norm(jnp.matmul(y, self.q_a_proj.w),
                            self.q_a_layernorm.w, c.rms_norm_eps)
            q = jnp.matmul(c_q, self.q_b_proj.w).reshape(
                b, s, c.num_attention_heads, -1)
            return (q[..., :c.qk_nope_head_dim],
                    _rope(q[..., c.qk_nope_head_dim:], positions, c))

    def _latent(self, y, positions):
        """[b, s, rank + rope_dim]: what the cache keeps of a token."""
        c = self.cfg
        kv_a = jnp.matmul(y, self.kv_a_proj_with_mqa.w)
        c_kv = _rms_norm(kv_a[..., :c.kv_lora_rank], self.kv_a_layernorm.w,
                         c.rms_norm_eps)
        k_rope = _rope(kv_a[..., c.kv_lora_rank:], positions, c)
        return jnp.concatenate([c_kv, k_rope], axis=-1)

    def _w_kvb(self):
        c = self.cfg
        return self.kv_b_proj.w.reshape(
            c.kv_lora_rank, c.num_attention_heads, -1)

    def forward(self, y, positions, cache=None):
        """y [b, s, hidden] after the block's first norm. ``cache`` None:
        expanded causal attention over the sequence itself."""
        c = self.cfg
        b, s, _ = y.shape
        scale = softmax_scale(c)
        q_nope, q_rope = self._queries(y, positions)
        if cache is None:
            lat = self._latent(y, positions)
            # the sequences themselves as a pool of one page a row
            o = lpa.latent_prefill_attention(
                q_nope, q_rope, lat,
                jnp.arange(b, dtype=jnp.int32)[:, None],
                jnp.zeros((b,), jnp.int32), self._w_kvb(), scale,
                c.kv_lora_rank, kv_limit=s)
            return jnp.matmul(o.reshape(b, s, -1), self.o_proj.w), None
        pool = cache["kv_pool"]
        ctx = cache["ctx_lens"].astype(jnp.int32)
        table, valid = cache["page_table"], cache["valid"]
        page_size = pool.shape[1]
        with jax.named_scope("latent_write"):
            lat = self._latent(y, positions)
            if pool.shape[-1] > lat.shape[-1]:   # a padded row
                lat = jnp.pad(lat, ((0, 0), (0, 0),
                                    (0, pool.shape[-1] - lat.shape[-1])))
            # as GPT's paged write: the index stays inside the table, dead
            # writes (padding, inactive slots) go to the null page
            page_idx = jnp.minimum(positions // page_size,
                                   table.shape[1] - 1)
            page_ids = jnp.take_along_axis(table, page_idx, axis=1)
            page_ids = jnp.where(valid, page_ids, 0)
            offsets = jnp.where(valid, positions % page_size, 0)
            pool = lpa.latent_write(pool, lat, page_ids, offsets)
        w_kvb = self._w_kvb()
        dn = c.qk_nope_head_dim
        if s == 1:
            with jax.named_scope("absorb"):
                q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0],
                                   w_kvb[..., :dn])
            o_lat = lpa.latent_decode_attention(
                q_lat, q_rope[:, 0], pool, table, ctx, scale)
            with jax.named_scope("absorb"):
                o = jnp.einsum("bhr,rhd->bhd", o_lat, w_kvb[..., dn:])
        else:
            limit = cache.get("kv_limit") or table.shape[1] * page_size
            o = lpa.latent_prefill_attention(
                q_nope, q_rope, pool, table, ctx, w_kvb, scale,
                c.kv_lora_rank, kv_limit=limit)
        out = jnp.matmul(o.reshape(b, s, -1), self.o_proj.w)
        new_cache = dict(cache, kv_pool=pool,
                         ctx_lens=ctx + jnp.sum(valid, axis=1,
                                                dtype=jnp.int32))
        return out, new_cache


class KimiK2MLP(nn.Layer):
    def __init__(self, hidden: int, width: int, std: float):
        super().__init__()
        self.gate_proj = _Weight((hidden, width), std)
        self.up_proj = _Weight((hidden, width), std)
        self.down_proj = _Weight((width, hidden), std)

    def forward(self, y):
        return _gated_mlp(y, self.gate_proj.w, self.up_proj.w,
                          self.down_proj.w)


class KimiK2Gate(nn.Layer):
    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        n = cfg.n_routed_experts
        self.weight = self.create_parameter(
            (cfg.hidden_size, n), attr=nn.ParamAttr(
                initializer=nn.initializer.Normal(0.0,
                                                  cfg.initializer_range)))
        self.e_score_correction_bias = self.create_parameter(
            (n,), attr=nn.ParamAttr(
                initializer=nn.initializer.Constant(0.0)))


class KimiK2Experts(nn.Layer):
    """The held experts' weights, stacked: ``[count, in, out]``."""

    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        count, h, f = cfg.held_experts[1], cfg.hidden_size, \
            cfg.moe_intermediate_size
        attr = lambda: nn.ParamAttr(  # noqa: E731
            initializer=nn.initializer.Normal(0.0, cfg.initializer_range))
        self.gate_proj = self.create_parameter((count, h, f), attr=attr())
        self.up_proj = self.create_parameter((count, h, f), attr=attr())
        self.down_proj = self.create_parameter((count, f, h), attr=attr())


class KimiK2MoE(nn.Layer):
    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        self.cfg = cfg
        self.gate = KimiK2Gate(cfg)
        self.experts = KimiK2Experts(cfg)
        self.shared_experts = KimiK2MLP(
            cfg.hidden_size,
            cfg.moe_intermediate_size * cfg.n_shared_experts,
            cfg.initializer_range)

    def forward(self, y, valid=None, shared: bool = True):
        """(out [b, s, hidden], counters int32 [4])."""
        c = self.cfg
        b, s, h = y.shape
        yt = y.reshape(b * s, h)
        with jax.named_scope("router"):
            w, idx = dm.route_sigmoid_topk(
                yt, self.gate.weight._value,
                self.gate.e_score_correction_bias._value,
                c.num_experts_per_tok, c.routed_scaling_factor,
                c.norm_topk_prob)
        e = self.experts
        out, counters = dm.dropless_experts(
            yt, w, idx, e.gate_proj._value, e.up_proj._value,
            e.down_proj._value, c.held_experts,
            None if valid is None else valid.reshape(b * s))
        if shared:
            with jax.named_scope("shared"):
                out = out + self.shared_experts(yt)
        return out.reshape(b, s, h), counters


class KimiK2DecoderLayer(nn.Layer):
    def __init__(self, cfg: KimiK2Config, index: int):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = _Weight((cfg.hidden_size,), None)
        self.self_attn = KimiK2Attention(cfg)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), None)
        self.is_moe = index >= cfg.first_k_dense_replace
        self.mlp = (KimiK2MoE(cfg) if self.is_moe else KimiK2MLP(
            cfg.hidden_size, cfg.intermediate_size, cfg.initializer_range))

    def forward(self, x, positions, cache=None):
        eps = self.cfg.rms_norm_eps
        with jax.named_scope("block/attn"):
            a, new_cache = self.self_attn(
                _rms_norm(x, self.input_layernorm.w, eps), positions, cache)
            x = x + a
        y = _rms_norm(x, self.post_attention_layernorm.w, eps)
        if not self.is_moe:
            with jax.named_scope("block/mlp"):
                return x + self.mlp(y), new_cache
        with jax.named_scope("block/moe"):
            m, counters = self.mlp(
                y, None if cache is None else cache["valid"])
        if new_cache is not None:
            new_cache["counters"] = counters
        return x + m, new_cache


class KimiK2Model(nn.Layer):
    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                    cfg.initializer_range)
        self.layers = nn.LayerList(
            [KimiK2DecoderLayer(cfg, i)
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
        with jax.named_scope("embed"):
            x = self.embed_tokens.w[ids]
        new_caches = []
        for i, layer in enumerate(self.layers):
            x, nc = layer(x, positions,
                          None if caches is None else caches[i])
            new_caches.append(nc)
        with jax.named_scope("final_norm"):
            x = _rms_norm(x, self.norm.w, self.cfg.rms_norm_eps)
        return x, (None if caches is None else new_caches)


class KimiK2ForCausalLM(nn.Layer):
    def __init__(self, cfg: KimiK2Config):
        super().__init__()
        self.cfg = cfg
        self.model = KimiK2Model(cfg)
        self.lm_head = _Weight((cfg.hidden_size, cfg.vocab_size),
                               cfg.initializer_range)

    def forward(self, input_ids, caches=None):
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        h, new_caches = self.model(ids.astype(jnp.int32), caches)
        with jax.named_scope("lm_head"):
            logits = Tensor(jnp.matmul(h, self.lm_head.w))
        return logits if caches is None else (logits, new_caches)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def decode_kernel_eligible(self, pages_per_seq: int, page_size: int,
                               quantized: bool = False) -> bool:
        """Whether the absorbed-decode kernel is dispatchable for a decode
        step of this model (``latent_paged_attention.mla_kernel_eligible``,
        the one gate)."""
        from ..kernels._common import on_tpu_backend
        from ..utils.flags import flag

        c = self.cfg
        return lpa.mla_kernel_eligible(
            c.num_attention_heads, lpa.padded_width(c.latent_width),
            c.kv_lora_rank, page_size, pages_per_seq,
            itemsize=self.model.embed_tokens.w.dtype.itemsize,
            on_tpu=on_tpu_backend(),
            flags_on=bool(flag("FLAGS_use_pallas_kernels", True)),
            interpret=bool(flag("FLAGS_ragged_interpret", False)))[0]

    # ------------------------------------------------ the serving contract
    def paged_cache_spec(self, kv_dtype: str = "float32",
                         tensor_parallel: int = 1,
                         speculative: bool = False):
        """What this model keeps in a paged cache, for ``ServingEngine``:
        one leaf a layer, ``kv_pool`` of ``rank + rope_dim`` values a token
        (padded to whole 128-lane rows) in the weights' dtype. Refuses, with the reason, what the latent
        model cannot do yet."""
        from ..serving.kv_cache import CacheLeaf, PagedCacheSpec

        c = self.cfg
        if tensor_parallel > 1:
            raise ValueError(
                "kimi_k2: tensor_parallel > 1 is not supported: the "
                "latent cache has no heads axis to shard, and serving/tp.py "
                "places GPT's leaves by name")
        if kv_dtype != "float32":
            raise ValueError(
                f"kimi_k2: kv_dtype={kv_dtype!r} is not supported: the "
                "int8 pool's scales are a page a HEAD, and a latent row "
                "has no heads (the pool takes the weights' dtype)")
        if speculative:
            raise ValueError(
                "kimi_k2: speculative decoding (spec=) is not supported: "
                "the verify step's K+1 tokens a slot have no absorbed "
                "attention path, and the draft proposer is a GPT")
        dtype = self.model.embed_tokens.w.dtype
        return PagedCacheSpec(
            num_layers=c.num_hidden_layers,
            max_seq_len=c.max_position_embeddings, dtype=dtype,
            # a row is padded with zeros to whole 128-lane rows (576 ->
            # 640), which is how the chip lays the pool out in any case
            # and what the decode kernel's page copies need
            leaves=(CacheLeaf("kv_pool",
                              (lpa.padded_width(c.latent_width),), dtype),),
            counters=MOE_COUNTERS)
