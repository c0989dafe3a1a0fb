"""Tensor-parallel sharded serving: the mesh, the Megatron weight shards,
and the ``shard_map`` wrappers that turn the engine's single-chip jitted
steps into sharded programs — compiled once per bucket, exactly like
single-chip serving, with exactly the collectives the partitioning implies.

Partitioning (Megatron-LM layout, restated for the engine's GPT):

- ``qkv_proj`` is COLUMN-parallel on the heads axis: each device holds the
  projection for ``num_heads / tp`` heads (the 3h output axis is laid out
  ``(3, heads, head_dim)``, so the global weight is head-permuted once,
  host-side, into per-device contiguous ``(3, local_heads, head_dim)``
  blocks before sharding). Attention itself is embarrassingly parallel
  over heads — no communication.
- The paged KV pool shards the SAME heads axis (``[pages, page_size,
  heads / tp, head_dim]`` per device): each device's pool shard holds its
  own heads' K/V, written by its own ``paged_write`` and read by its own
  gather — page ids stay LOGICAL and host-side (one allocator, one page
  table, one prefix-cache index for all shards), so refcounts, COW, and
  eviction are completely sharding-agnostic.
- ``out_proj`` and ``fc2`` are ROW-parallel: each device contracts its
  local heads / ffn shard and ONE ``lax.psum`` per site restores the
  replicated residual stream (the ``2 * num_layers`` per-step all-reduces
  in the declared budget). Their biases must be added exactly once, not
  ``tp`` times: the global bias is stacked ``[tp, dim]`` with the real
  bias on device 0 and zeros elsewhere, so the psum reassembles it
  bit-exactly (no rescaling tricks).
- ``fc1`` is column-parallel (``gelu`` is elementwise — no communication);
  embeddings, layer norms, and the LM head weight are replicated. The LM
  head CONTRACTION (hidden axis) is sharded at trace time instead
  (text/gpt.py ``_tp_logits``): one psum of the logits partials — the
  "+1 for the logits" in the budget — splits the head FLOPs without
  touching the embedding lookup.

Every per-step collective is therefore declared, countable, and certified:
``TPContext.step_budget`` returns the ``CollectiveBudget``
(``all_reduce = 2 * num_layers + 1``, byte-capped) that
``ServingConfig(debug_checks=True)`` enforces on the compiled artifact at
each program's first trace — the same hlocheck audit single-chip steps
pass at budget ZERO.

The wrappers run the UNCHANGED engine step bodies inside ``shard_map``
(params/pools sharded, everything else replicated, ``check_vma=False`` —
the outputs are replicated by construction: every device computes the
same post-psum values). The engine's CompileGuards wrap the sharded
callables exactly as they wrap the single-chip ones, so ``compile_counts``
and the retrace/donation audits are sharding-blind.
"""
from __future__ import annotations

import numpy as np

from ..analysis.hlocheck import CollectiveBudget

__all__ = ["TPContext", "quantized_psum"]


def quantized_psum(x, axis: str):
    """EQuARX-style quantized all-reduce: ship int8 codes instead of f32.

    Each shard quantizes against a SHARED step derived from the psum of
    the per-shard absmaxes — a 4-byte scalar all-reduce — then psums the
    int8 codes and dequantizes. The payload for a ``[.., vocab]`` logits
    reduction shrinks 4x (f32 -> s8), at bounded quantization error.

    The step is ``psum(absmax) / (127 - n)`` (``n`` = axis size, resolved
    statically — no collective), NOT ``absmax / 127``: with ``n`` shards
    each contributing codes up to ``amax_i/step + 1/2`` in magnitude, the
    accumulated int8 sum is bounded by ``sum(amax_i)/step + n/2 =
    (127 - n) + n/2 < 127`` — the all-reduce itself can never overflow
    the int8 accumulator, for any shard count and any input. ``step`` is
    identical on every shard (it is a psum result), so dequantization is
    replicated bit-exactly.

    This is the serving stack's ONE quantized collective entry point —
    flag-gated by ``ServingConfig(tp_quantized_logits=True)`` and routed
    through ``text/gpt.py::_tp_logits``; its budget shape (one extra tiny
    all-reduce, int8 payload) is declared by ``TPContext.step_budget``
    and certified bit-accurately by hlocheck's sub-byte dtype census."""
    import jax.numpy as jnp
    from jax import lax  # lint: disable=PT015 — the sanctioned wrapper

    n = lax.psum(1, axis)  # axis size: constant-folded, no collective
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    total = lax.psum(amax, axis)            # 4-byte scale all-reduce
    step = total / jnp.float32(127 - n)
    step = jnp.where(step > 0, step, jnp.float32(1.0))  # all-zero input
    codes = jnp.clip(jnp.round(x / step), -127, 127).astype(jnp.int8)
    ysum = lax.psum(codes, axis)            # the int8 payload all-reduce
    return ysum.astype(x.dtype) * step.astype(x.dtype)

#: the paged pool's sharded axis: [num_pages, page_size, HEADS, head_dim]
_POOL_AXES = (None, None, "tp", None)
#: a quantized pool's per-page scales: [num_pages, HEADS]
_SCALE_AXES = (None, "tp")
#: a swap gather/scatter payload: [layers, pages, page_size, HEADS, head_dim]
_KV_STACK_AXES = (None, None, None, "tp", None)
#: a swap payload's scale stack: [layers, pages, HEADS]
_SCALE_STACK_AXES = (None, None, "tp")


class TPContext:
    """Everything ``ServingConfig(tensor_parallel=N)`` needs: the N-device
    mesh, the parameter shard specs (+ the host-side layout transforms a
    contiguous shard requires), the pool sharding, and the ``shard_map``
    wrappers for the engine and cache jits."""

    AXIS = "tp"

    def __init__(self, degree: int, model_cfg, devices=None, *,
                 overlap_scheduler: bool = False,
                 quantized_logits: bool = False):
        import jax
        from jax.sharding import Mesh

        devs = list(devices if devices is not None else jax.devices())
        if degree < 2:
            raise ValueError(f"tensor_parallel={degree}: a mesh needs at "
                             f"least 2 devices (1 = single-chip serving)")
        if len(devs) < degree:
            raise ValueError(
                f"tensor_parallel={degree} but only {len(devs)} device(s) "
                f"visible — on CPU, force a wider mesh with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={degree}")
        for what, dim in (("num_heads", model_cfg.num_heads),
                          ("hidden_size", model_cfg.hidden_size),
                          ("ffn_hidden", model_cfg.ffn_hidden)):
            if dim % degree:
                raise ValueError(
                    f"tensor_parallel={degree} must divide the model's "
                    f"{what}={dim} (heads shard the KV pool, ffn shards "
                    f"the MLP, hidden shards the LM-head contraction)")
        self.degree = degree
        self.model_cfg = model_cfg
        # latency hiding: ask XLA to schedule each psum's -start/-done
        # pair around independent compute (ServingConfig(
        # tp_overlap_scheduler=True)); when on, step_budget demands every
        # async collective actually overlap (min_overlap_frac=1.0) —
        # vacuous on backends that compile collectives sync (CPU)
        self.overlap_scheduler = bool(overlap_scheduler)
        # EQuARX-style int8 logits all-reduce (quantized_psum above),
        # routed through text/gpt.py's _tp_logits at trace time
        self.quantized_logits = bool(quantized_logits)
        self.mesh = Mesh(np.array(devs[:degree]), (self.AXIS,))
        self.param_specs: dict[str, object] = {}

    # ----------------------------------------------------------- placement
    def _sharding(self, *axes):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return NamedSharding(self.mesh, P(*axes))

    def _spec_and_transform(self, name: str, arr):
        """(transformed global array, PartitionSpec axes) for one weight.

        The transforms keep every device's shard CONTIGUOUS in the global
        array so a plain one-axis PartitionSpec shards it correctly:
        qkv weights/biases are head-permuted from ``(3, heads, dim)`` to
        ``(tp, 3, heads/tp, dim)`` blocks, and row-parallel biases are
        stacked ``[tp, dim]`` with zeros beyond device 0 (added exactly
        once by the psum; a ``[1, dim]`` local shard broadcasts like the
        ``[dim]`` original)."""
        c, n = self.model_cfg, self.degree
        heads, hd = c.num_heads, c.hidden_size // c.num_heads
        if name.endswith("qkv_proj.weight"):
            h = arr.shape[0]
            w = arr.reshape(h, 3, n, heads // n, hd)
            return (w.transpose(0, 2, 1, 3, 4).reshape(h, -1),
                    (None, self.AXIS))
        if name.endswith("qkv_proj.bias"):
            b = arr.reshape(3, n, heads // n, hd)
            return b.transpose(1, 0, 2, 3).reshape(-1), (self.AXIS,)
        if name.endswith("out_proj.weight") or name.endswith("fc2.weight"):
            return arr, (self.AXIS, None)  # row-parallel: contract local shard
        if name.endswith("out_proj.bias") or name.endswith("fc2.bias"):
            stacked = np.zeros((n,) + arr.shape, arr.dtype)
            stacked[0] = arr
            return stacked, (self.AXIS, None)
        if name.endswith("fc1.weight"):
            return arr, (None, self.AXIS)  # column-parallel
        if name.endswith("fc1.bias"):
            return arr, (self.AXIS,)
        return arr, ()  # embeddings / norms / LM head: replicated

    def shard_params(self, params: dict) -> dict:
        """Place every parameter on the mesh under its Megatron spec
        (recording the specs for the step wrappers); returns the placed
        dict the engine passes to every step call."""
        import jax
        from jax.sharding import PartitionSpec as P

        placed = {}
        for name, arr in params.items():
            arr, axes = self._spec_and_transform(name, np.asarray(arr))
            self.param_specs[name] = P(*axes)
            placed[name] = jax.device_put(arr, self._sharding(*axes))
        return placed

    def replicated(self, arr):
        """``arr`` on every device of the mesh: the placement of a step's
        replicated token output, for an operand that stands in for one."""
        import jax

        return jax.device_put(arr, self._sharding())

    def _pool_specs(self, num_layers: int, quantized: bool = False):
        from jax.sharding import PartitionSpec as P

        spec = P(*_POOL_AXES)
        leaf = {"k_pool": spec, "v_pool": spec}
        if quantized:
            # the per-page-per-head scales shard the SAME heads axis as
            # the codes they dequantize — every device dequantizes its own
            # heads locally, so quantization adds zero collectives
            leaf |= {"k_scale": P(*_SCALE_AXES), "v_scale": P(*_SCALE_AXES)}
        return [dict(leaf) for _ in range(num_layers)]

    def pool_shardings(self):
        """(pool sharding, scale sharding): the paged pools shard their
        heads axis, and quantized, their per-page scale leaves the same
        axis. ``kv_cache.init_pools`` creates every leaf under these."""
        return self._sharding(*_POOL_AXES), self._sharding(*_SCALE_AXES)

    # -------------------------------------------------------- step wrappers
    def _shard_map(self, fn, in_specs, out_specs):
        # the ONE sanctioned shard_map entry point of the serving stack:
        # every wrapped step is registered with a declared CollectiveBudget
        # in the hlocheck registry (tp2_engine_prefill/_prefill_chunk/
        # _decode + the per-shard cache movers) and certified under
        # debug_checks — exactly what lint rule PT010 exists to enforce
        import jax

        return jax.shard_map(  # lint: disable=PT010
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)

    def wrap_step(self, fn, num_layers: int, n_rest: int,
                  quantized: bool = False):
        """The engine step wrapper: ``fn(params, pools, *rest) ->
        (new_pools, tok)`` becomes a sharded program — params and pools
        enter under their shard specs, the ``n_rest`` host-built operands
        (ids, page rows, scalars) replicated — with the model's
        tensor-parallel psums enabled for the trace (text/gpt.py
        ``tp_axis``). Outputs: pools sharded as they came, the sampled
        token replicated (every device computed the same post-psum
        logits)."""
        from jax.sharding import PartitionSpec as P

        from ..text.gpt import tp_axis

        def stepped(p, pools, *rest):
            with tp_axis(self.AXIS,
                         quantized_logits=self.quantized_logits):
                return fn(p, pools, *rest)

        pool = self._pool_specs(num_layers, quantized)
        return self._shard_map(
            stepped,
            in_specs=(dict(self.param_specs), pool) + (P(),) * n_rest,
            out_specs=(pool, P()))

    def wrap_cache(self, fn, kind: str, num_layers: int,
                   quantized: bool = False):
        """The paged cache's data movers, per-shard: the swap gather reads
        each device's pool shard into its slice of the layer-stacked
        [layers, pages, page_size, heads, head_dim] payload (host side
        reassembles the full handle), the swap scatter and COW copy write
        each shard in place. Quantized pools move their int8 codes plus
        the heads-sharded scale stacks the same way. Pure data movement on
        logical page indices — zero collectives, certified by the
        tp2_swap/cow registry steps."""
        from jax.sharding import PartitionSpec as P

        pool = self._pool_specs(num_layers, quantized)
        kv = P(*_KV_STACK_AXES)
        sc = P(*_SCALE_STACK_AXES)
        in_specs, out_specs = {
            "gather": ((pool, P()),
                       (kv, kv, sc, sc) if quantized else (kv, kv)),
            "scatter": ((pool, P(), kv, kv) + ((sc, sc) if quantized
                                               else ()), pool),
            "copy": ((pool, P(), P()), pool),
        }[kind]
        return self._shard_map(fn, in_specs=in_specs, out_specs=out_specs)

    # ------------------------------------------------------------- budgets
    def compiler_options(self) -> dict | None:
        """Per-jit XLA options for the sharded engine steps: the latency-
        hiding scheduler (overlap each psum's async -start/-done with
        independent compute), on backends that implement it. CPU's
        collectives compile sync — no scheduler to engage — so this
        returns None there and the steps compile exactly as before; the
        overlap contract is still DECLARED (step_budget's
        min_overlap_frac) and enforced wherever async pairs appear."""
        if not self.overlap_scheduler:
            return None
        import jax

        if jax.default_backend() != "tpu":
            return None
        return {"xla_tpu_enable_latency_hiding_scheduler": True}

    def step_budget(self, batch: int, seq: int,
                    itemsize: int = 4) -> CollectiveBudget:
        """The collectives one sharded engine step implies — nothing more:
        two all-reduces per transformer block (row-parallel attention
        out_proj + row-parallel MLP fc2, each ``[batch, seq, hidden]``)
        plus one for the logits (``[batch, seq, vocab]``), byte-capped at
        exactly that payload. An implicit resharding collective XLA
        sneaks in lands over this budget and fails the hlocheck audit.

        With ``quantized_logits`` the logits reduction becomes TWO
        all-reduces — the 4-byte shared-scale psum plus the int8 codes —
        so the count is ``2L + 2`` and the logits payload shrinks 4x
        (counted bit-accurately by hlocheck's dtype census). With
        ``overlap_scheduler`` the budget additionally demands that every
        collective XLA compiles async actually overlaps compute
        (``min_overlap_frac=1.0``; vacuous when compiled sync)."""
        c = self.model_cfg
        per_block = batch * seq * c.hidden_size * itemsize
        if self.quantized_logits:
            extra_ar, logits = 1, batch * seq * c.vocab_size * 1 + 4
        else:
            extra_ar, logits = 0, batch * seq * c.vocab_size * itemsize
        return CollectiveBudget(
            all_reduce=2 * c.num_layers + 1 + extra_ar,
            max_collective_bytes=2 * c.num_layers * per_block + logits,
            min_overlap_frac=1.0 if self.overlap_scheduler else 0.0)
