"""Granite 4.0-H (``model_type`` ``granitemoehybrid``): a hybrid decoder of
Mamba-2 state-space layers (Dao & Gu 2024, arXiv:2405.21060) with a few
causal-attention layers among them (``layer_types``), every layer followed
by a gated SiLU MLP, under Granite's four multipliers and a tied head.

    x0 = embedding_multiplier * E[ids]
    x += residual_multiplier * Mixer(RMSNorm(x))
    x += residual_multiplier * MLP(RMSNorm(x)),  MLP(y) = W_out(silu(g) * u),
                                                 [g | u] = y W_in
    logits = RMSNorm(x) E^T / logits_scaling

An ``attention`` layer's mixer: grouped KV heads, NO positional encoding
(``position_embedding_type`` "nope"), scores scaled by
``attention_multiplier`` (not ``head_dim ** -0.5``). A ``mamba`` layer's:

    [z | xBC | dt] = y W_in
    xBC_t = silu(b + sum_j w_j * xBC_{t-(d_conv-1)+j})     depthwise, causal
    [x | B | C] = xBC;  D_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
    S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t             a head, [p, n]
    y_t = S_t C_t + D * x_t
    out = (RMSNorm(y * silu(z)) * w) W_out                  one norm group

Serving only: ``forward(ids)`` is the whole pass from empty states, and
``forward(ids, caches=...)`` the paged-cache contract that
``serving.ServingEngine`` calls (see ``paged_cache_spec``). What a layer
keeps differs by its kind: an attention layer ``k_pool`` / ``v_pool`` a
TOKEN, in pages; a Mamba layer ``ssm_state`` (float32) and ``conv_state``
(the last ``d_conv - 1`` rows of ``xBC``) a SLOT, whatever its length. A
call of several tokens a row (prefill, a chunk) runs the scan in chunks of
``mamba_chunk_size`` as composite XLA from the slot's state (from zeros at
position 0) and stops at the row's last real token; a call of one token a
slot (decode) is the recurrence, one Pallas kernel
(``kernels/ssm_state_update.py``).

Left out: the routed experts of the family's larger siblings
(``num_local_experts`` 0 here: no router), rotary positions (``rope_theta``
is unused under "nope"), ``mamba_n_groups`` other than 1, projection biases
(``mamba_proj_bias``, ``attention_bias`` false as published).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..kernels import ssm_state_update as ssu

__all__ = ["GraniteHybridConfig", "GraniteHybridForCausalLM",
           "SSM_COUNTERS", "ssd_chunked", "ssd_sequential"]

#: the step counters a paged call reports (``new_cache["counters"]`` of a
#: Mamba layer, int32 [2]), under the names the engine publishes them:
#: slots whose state the launch advanced, and state rows (a slot's state
#: of one layer) the update read and wrote
SSM_COUNTERS = ("ssm_state_rows_live_total", "ssm_state_rows_moved_total")

_HIGHEST = jax.lax.Precision.HIGHEST


def _layer_types():
    return ["attention" if i % 10 == 5 else "mamba" for i in range(40)]


@dataclass
class GraniteHybridConfig:
    """The model's ``config.json``, key for key; the defaults are
    granite-4.0-h-micro's published values."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: list = field(default_factory=_layer_types)
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    position_embedding_type: str = "nope"
    tie_word_embeddings: bool = True
    num_local_experts: int = 0
    initializer_range: float = 0.02

    def __post_init__(self):
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {"mamba", "attention"}:
            raise ValueError("layer_types names 'mamba' or 'attention' for "
                             "each of num_hidden_layers layers")
        if self.mamba_n_heads * self.mamba_d_head \
                != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads x mamba_d_head is not "
                             "mamba_expand x hidden_size")
        if self.mamba_n_groups != 1:
            raise ValueError("only mamba_n_groups = 1 is here (one B and C "
                             "for all heads, as published)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads do not group over the KV heads")
        if self.position_embedding_type != "nope":
            raise ValueError("only position_embedding_type 'nope' is here")
        if self.num_local_experts:
            raise ValueError("routed experts (num_local_experts > 0) are "
                             "not here: the dense sibling only")
        if not self.tie_word_embeddings:
            raise ValueError("only the tied head is here")

    # what the serving engine reads of any model's config
    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_width(self) -> int:
        """``xBC``: the inner width and one B and one C a group."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state


# ------------------------------------------------------------------ the scan
def ssd_sequential(x, dt, a, b_in, c_out, s0):
    """The recurrence token by token (``lax.scan``), float32: x ``[b, s,
    h, p]``, dt ``[b, s, h]`` (0 where a position is padding: the state
    then stays), a ``[h]``, b_in and c_out ``[b, s, n]``, s0 ``[b, h, p,
    n]`` -> (y ``[b, s, h, p]``, final state)."""
    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        new, y = ssu.ssm_update_reference(state, x_t, dt_t, a, b_t, c_t)
        return new, y

    seq = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
    final, ys = jax.lax.scan(step, s0.astype(jnp.float32),
                             (seq(x), seq(dt), seq(b_in), seq(c_out)))
    return jnp.moveaxis(ys, 0, 1), final


def ssd_chunked(x, dt, a, b_in, c_out, s0, chunk: int):
    """The same recurrence in chunks of ``chunk`` tokens (the state-space
    duality of arXiv:2405.21060 section 6): inside a chunk the outputs are
    a masked, decayed ``C B^T`` product over the chunk's own tokens, the
    chunks' end states are carried by a short scan, and each chunk's start
    state adds its decayed readout. Shapes and dtype as
    :func:`ssd_sequential`; a position with ``dt = 0`` neither decays the
    state nor adds to it."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    q = min(chunk, s)
    pad = -s % q
    if pad:
        widths = lambda v: [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2)  # noqa: E731
        x, dt, b_in, c_out = (jnp.pad(v, widths(v))
                              for v in (x, dt, b_in, c_out))
    nc = (s + pad) // q
    x, dt = x.astype(f32), dt.astype(f32)
    cb = lambda v: v.astype(f32).reshape((b, nc, q) + v.shape[2:])  # noqa: E731
    xb = cb(x * dt[..., None])                                # [b,c,q,h,p]
    b_c, c_c = cb(b_in), cb(c_out)                            # [b,c,q,n]
    # log-decay up to and with each token, a head a row: [b,c,h,q]
    cum = jnp.cumsum(cb(dt * a.astype(f32)), axis=2).transpose(0, 1, 3, 2)
    ein = lambda spec, *ops: jnp.einsum(spec, *ops, precision=_HIGHEST)  # noqa: E731
    # inside a chunk: token t reads token s <= t through exp(cum_t - cum_s)
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                      # [b,c,h,t,s]
    scores = ein("bctn,bcsn->bcts", c_c, b_c)[:, :, None] * decay
    y = ein("bchts,bcshp->bcthp", scores, xb)
    # what each chunk adds to its end state, and the chunk's whole decay
    to_end = jnp.exp(cum[..., -1:] - cum)                     # [b,c,h,s]
    added = ein("bchs,bcshp,bcsn->bchpn", to_end, xb, b_c)
    whole = jnp.exp(cum[..., -1])                             # [b,c,h]

    def carry(state, inp):
        dec, add = inp
        return dec[..., None, None] * state + add, state

    final, starts = jax.lax.scan(
        carry, s0.astype(f32),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                       # [b,c,h,p,n]
    y = y + ein("bctn,bchpn,bcht->bcthp", c_c, starts, jnp.exp(cum))
    return y.reshape(b, nc * q, h, p)[:, :s], final


# -------------------------------------------------------------------- layers
def _rms_norm(x, w, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


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


class _Conv(nn.Layer):
    """The depthwise causal convolution's taps ``[d_conv, width]`` (tap j
    multiplies the token ``d_conv - 1 - j`` back) and its bias."""

    def __init__(self, taps: int, width: int, std: float):
        super().__init__()
        self.weight = self.create_parameter(
            (taps, width), attr=nn.ParamAttr(
                initializer=nn.initializer.Normal(0.0, std)))
        self.bias = self.create_parameter(
            (width,), attr=nn.ParamAttr(
                initializer=nn.initializer.Constant(0.0)))


class GraniteMamba2Mixer(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        h, std = cfg.hidden_size, cfg.initializer_range
        nh = cfg.mamba_n_heads
        self.in_proj = _Weight((h, cfg.d_inner + cfg.conv_width + nh), std)
        self.conv1d = _Conv(cfg.mamba_d_conv, cfg.conv_width, std)
        const = lambda v: nn.ParamAttr(  # noqa: E731
            initializer=nn.initializer.Constant(v))
        # A = -exp(A_log) = -1 and a step of softplus(0) = 0.69 until real
        # weights (or the benchmark's draw) are installed
        self.A_log = self.create_parameter((nh,), attr=const(0.0))
        self.D = self.create_parameter((nh,), attr=const(1.0))
        self.dt_bias = self.create_parameter((nh,), attr=const(0.0))
        self.norm = _Weight((cfg.d_inner,), None)
        self.out_proj = _Weight((cfg.d_inner, h), std)

    def forward(self, y, cache=None):
        """y [b, s, hidden] after the block's first norm -> (out, the
        layer's new cache or None)."""
        c = self.cfg
        f32 = jnp.float32
        b, s, _ = y.shape
        nh, p, n = c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state
        keep = c.mamba_d_conv - 1
        with jax.named_scope("in_proj"):
            zxbcdt = jnp.matmul(y, self.in_proj.w)
            z = zxbcdt[..., :c.d_inner]
            xbc = zxbcdt[..., c.d_inner:c.d_inner + c.conv_width]
            dt = zxbcdt[..., c.d_inner + c.conv_width:]
        if cache is None:
            valid = jnp.ones((b, s), bool)
            slots = fresh = None
            conv0 = jnp.zeros((b, keep, c.conv_width), xbc.dtype)
            state0 = jnp.zeros((b, nh, p, n), f32)
        else:
            valid, slots = cache["valid"], cache.get("slots")
            conv_pool, state_pool = cache["conv_state"], cache["ssm_state"]
            if slots is None:   # row i is slot i: a decode step
                if s != 1:
                    raise NotImplementedError(
                        "granite_hybrid: several tokens a slot for every "
                        "slot at once (a verify step) has no state path")
                conv0 = conv_pool
            else:
                # a request that starts at position 0 starts from zeros,
                # whatever the slot's last owner left: in the program
                fresh = (cache["ctx_lens"] == 0)[:, None, None]
                conv0 = jnp.where(fresh, 0, conv_pool[slots])
                state0 = jnp.where(fresh[..., None], 0.0, state_pool[slots])
        tail = jnp.sum(valid, axis=1, dtype=jnp.int32)           # [b]
        with jax.named_scope("conv"):
            seq = jnp.concatenate([conv0.astype(xbc.dtype), xbc], axis=1)
            w = self.conv1d.weight._value.astype(f32)
            acc = self.conv1d.bias._value.astype(f32)
            for j in range(c.mamba_d_conv):
                acc = acc + w[j] * seq[:, j:j + s].astype(f32)
            act = jax.nn.silu(acc)                                # [b,s,w]
            # the rows of the row's last real tokens: a padded position
            # leaves none behind
            conv_new = jax.vmap(lambda r, t: jax.lax.dynamic_slice_in_dim(
                r, t, keep, axis=0))(seq, tail)
        x = act[..., :c.d_inner].reshape(b, s, nh, p)
        b_in = act[..., c.d_inner:c.d_inner + n]
        c_out = act[..., c.d_inner + n:]
        # a padded position has a step of 0: decay 1 and no input
        step = jnp.where(valid[..., None], jax.nn.softplus(
            dt.astype(f32) + self.dt_bias._value.astype(f32)), 0.0)
        a = -jnp.exp(self.A_log._value.astype(f32))
        new_cache = None
        if cache is not None and slots is None:
            with jax.named_scope("update"):
                live = valid[:, 0]
                state_new, ys, moved = ssu.ssm_update(
                    state_pool, x[:, 0], step[:, 0], a, b_in[:, 0],
                    c_out[:, 0], live)
                ys = ys[:, None]
            counters = jnp.stack([jnp.sum(live, dtype=jnp.int32), moved])
            new_cache = dict(cache, ssm_state=state_new,
                             conv_state=conv_new.astype(conv_pool.dtype),
                             counters=counters)
        else:
            with jax.named_scope("scan"):
                ys, state1 = ssd_chunked(x, step, a, b_in, c_out, state0,
                                         c.mamba_chunk_size)
            if cache is not None:
                rows = jnp.int32(b)
                new_cache = dict(
                    cache, ssm_state=state_pool.at[slots].set(state1),
                    conv_state=conv_pool.at[slots].set(
                        conv_new.astype(conv_pool.dtype)),
                    counters=jnp.stack([rows, rows]))
        with jax.named_scope("gate_norm"):
            ys = ys + self.D._value.astype(f32)[:, None] * x
            g = ys.reshape(b, s, c.d_inner) * jax.nn.silu(z.astype(f32))
            g = _rms_norm(g, self.norm.w, c.rms_norm_eps).astype(y.dtype)
        with jax.named_scope("out_proj"):
            return jnp.matmul(g, self.out_proj.w), new_cache


class GraniteAttention(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        h, d, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        self.q_proj = _Weight((h, cfg.num_attention_heads * d), std)
        self.k_proj = _Weight((h, cfg.num_key_value_heads * d), std)
        self.v_proj = _Weight((h, cfg.num_key_value_heads * d), std)
        self.o_proj = _Weight((cfg.num_attention_heads * d, h), std)

    def forward(self, y, cache=None):
        from ..kernels import paged_attention as pa
        from ..kernels.attention import sdpa_reference

        c = self.cfg
        b, s, _ = y.shape
        d, scale = c.head_dim, c.attention_multiplier
        q = jnp.matmul(y, self.q_proj.w).reshape(b, s, -1, d)
        k = jnp.matmul(y, self.k_proj.w).reshape(b, s, -1, d)
        v = jnp.matmul(y, self.v_proj.w).reshape(b, s, -1, d)
        q = q.transpose(0, 2, 1, 3)                           # [b,H,s,d]
        if cache is None:
            g = c.num_attention_heads // c.num_key_value_heads
            o = sdpa_reference(
                q.reshape(b, -1, g, s, d),
                k.transpose(0, 2, 1, 3)[:, :, None],
                v.transpose(0, 2, 1, 3)[:, :, None], is_causal=True,
                scale=scale).reshape(b, -1, s, d)
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
            # grouped heads over the flat pool: a decode step runs the
            # grouped-head kernel, a call of several tokens a row the
            # composite (kernels/paged_attention.py's dispatch)
            o = pa.paged_attention(q, k_pool, v_pool, table, ctx,
                                   scale=scale)
            new_cache = dict(cache, k_pool=k_pool, v_pool=v_pool,
                             ctx_lens=ctx + jnp.sum(valid, axis=1,
                                                    dtype=jnp.int32))
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1).astype(y.dtype)
        return jnp.matmul(o, self.o_proj.w), new_cache


class GraniteSharedMLP(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        h, f, std = cfg.hidden_size, cfg.shared_intermediate_size, \
            cfg.initializer_range
        self.input_linear = _Weight((h, 2 * f), std)
        self.output_linear = _Weight((f, h), std)

    def forward(self, y):
        gu = jnp.matmul(y, self.input_linear.w)
        f = gu.shape[-1] // 2
        return jnp.matmul(jax.nn.silu(gu[..., :f]) * gu[..., f:],
                          self.output_linear.w)


class GraniteHybridDecoderLayer(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.kind = cfg.layer_types[index]
        self.input_layernorm = _Weight((cfg.hidden_size,), None)
        if self.kind == "mamba":
            self.mamba = GraniteMamba2Mixer(cfg)
        else:
            self.self_attn = GraniteAttention(cfg)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), None)
        self.shared_mlp = GraniteSharedMLP(cfg)

    def forward(self, x, cache=None):
        c = self.cfg
        y = _rms_norm(x, self.input_layernorm.w, c.rms_norm_eps)
        if self.kind == "mamba":
            with jax.named_scope("block/ssm"):
                m, new_cache = self.mamba(y, cache)
        else:
            with jax.named_scope("block/attn"):
                m, new_cache = self.self_attn(y, cache)
        x = x + c.residual_multiplier * m
        with jax.named_scope("block/mlp"):
            y = _rms_norm(x, self.post_attention_layernorm.w,
                          c.rms_norm_eps)
            return x + c.residual_multiplier * self.shared_mlp(y), new_cache


class GraniteHybridModel(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                    cfg.initializer_range)
        self.layers = nn.LayerList(
            [GraniteHybridDecoderLayer(cfg, i)
             for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), None)

    def forward(self, ids, caches=None):
        c = self.cfg
        with jax.named_scope("embed"):
            w = self.embed_tokens.w
            x = (w[ids].astype(jnp.float32)
                 * c.embedding_multiplier).astype(w.dtype)
        new_caches = []
        for i, layer in enumerate(self.layers):
            x, nc = layer(x, None if caches is None else caches[i])
            new_caches.append(nc)
        with jax.named_scope("final_norm"):
            x = _rms_norm(x, self.norm.w, c.rms_norm_eps)
        return x, (None if caches is None else new_caches)


class GraniteHybridForCausalLM(nn.Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.model = GraniteHybridModel(cfg)

    def forward(self, input_ids, caches=None):
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        h, new_caches = self.model(ids.astype(jnp.int32), caches)
        with jax.named_scope("lm_head"):
            # the tied head: the embedding table, transposed
            logits = jnp.einsum("bsh,vh->bsv", h,
                                self.model.embed_tokens.w,
                                preferred_element_type=jnp.float32)
            # float32: a greedy choice between near-equal logits is made
            # on what the accumulator held, not on its rounding
            logits = Tensor(logits / self.cfg.logits_scaling)
        return logits if caches is None else (logits, new_caches)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def decode_kernel_eligible(self, pages_per_seq: int, page_size: int,
                               quantized: bool = False) -> bool:
        """Whether the decode step's state update reaches its Pallas
        kernel (``ssm_state_update.ssm_kernel_eligible``, the one gate).
        The attention layers reach theirs by a gate of their own
        (``paged_decode.gqa_kernel_eligible``, asked by
        ``paged_attention``'s dispatch at every call; what they stage is
        counted through ``paged_cache_spec``'s ``pages_staged``)."""
        from ..kernels._common import on_tpu_backend
        from ..utils.flags import flag

        c = self.cfg
        return ssu.ssm_kernel_eligible(
            c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
            on_tpu=on_tpu_backend(),
            flags_on=bool(flag("FLAGS_use_pallas_kernels", True)),
            interpret=bool(flag("FLAGS_ragged_interpret", False)))[0]

    # ------------------------------------------------ the serving contract
    def paged_cache_spec(self, kv_dtype: str = "float32",
                         tensor_parallel: int = 1,
                         speculative: bool = False):
        """What this model keeps, for ``ServingEngine``, layer by layer: an
        attention layer ``k_pool`` and ``v_pool`` of ``kv_heads x
        head_dim`` values a token (flat: whole lane rows) in the weights'
        dtype; a Mamba layer
        ``ssm_state`` ``[heads, head_dim, d_state]`` float32 and
        ``conv_state`` ``[d_conv - 1, conv_width]`` a SLOT. Its cache
        cannot be shared by prefix. Refuses, with the reason, what a model
        with a recurrent state cannot do yet."""
        from ..kernels.paged_attention import grouped_pages_staged_fn
        from ..serving.kv_cache import CacheLeaf, PagedCacheSpec

        c = self.cfg
        if tensor_parallel > 1:
            raise ValueError(
                "granite_hybrid: tensor_parallel > 1 is not supported: "
                "a slot's state has no placement under serving/tp.py, "
                "which places GPT's leaves by name")
        if kv_dtype != "float32":
            raise ValueError(
                f"granite_hybrid: kv_dtype={kv_dtype!r} is not supported: "
                "the int8 pool's write and gather are GPT's, and a "
                "recurrent state has no page to scale (the pools take the "
                "weights' dtype, the state float32)")
        if speculative:
            raise ValueError(
                "granite_hybrid: speculative decoding (spec=) is not "
                "supported: a rejected draft token cannot be taken back "
                "out of a recurrent state")
        dtype = self.model.embed_tokens.w.dtype
        # a token's KV heads flat, 8 x 64 = 512 values: at head size 64 a
        # pool of [.., 8, 64] is half-empty lane rows, and the chip's
        # compiler laid the whole 211 MB pool out anew three times a layer
        # a step to gather from it (33 of a decode step's 65 ms, my chip
        # run, PR 33)
        kv_width = c.num_key_value_heads * c.head_dim
        attention = (CacheLeaf("k_pool", (kv_width,), dtype),
                     CacheLeaf("v_pool", (kv_width,), dtype))
        mamba = (
            CacheLeaf("ssm_state", (c.mamba_n_heads, c.mamba_d_head,
                                    c.mamba_d_state), jnp.float32,
                      per_slot=True),
            CacheLeaf("conv_state", (c.mamba_d_conv - 1, c.conv_width),
                      dtype, per_slot=True))

        def pages_staged(num_query_tokens, pages_per_seq, page_size):
            # an attention layer's worth, by the path the call takes
            return grouped_pages_staged_fn(
                c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                page_size, pages_per_seq, num_query_tokens,
                itemsize=dtype.itemsize)

        return PagedCacheSpec(
            num_layers=c.num_hidden_layers,
            max_seq_len=c.max_position_embeddings, dtype=dtype,
            leaves_by_layer=tuple(mamba if t == "mamba" else attention
                                  for t in c.layer_types),
            counters=SSM_COUNTERS, pages_staged=pages_staged,
            no_prefix_sharing=(
                "granite_hybrid: a shared page of keys and values needs "
                "the recurrent state that went with its last token, and "
                "no snapshot of a slot's state at a page boundary exists"))
