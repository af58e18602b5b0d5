"""PARALLEL-MEM-SGD on a TPU mesh: per-worker memory + sparse all-gather.

This is the distributed heart of the framework. It runs INSIDE a
``jax.shard_map`` that is *manual* over the data-parallel mesh axes
(``('data',)`` or ``('pod', 'data')``) and *auto* (GSPMD) over the
``model`` axis. Each data-parallel worker:

  1. holds its own error-feedback memory m_w (paper Algorithm 2),
  2. forms u_w = m_w + eta * g_w for its local gradient g_w,
  3. selects ROW-BLOCK top-k (values, indices) per tensor (see below),
  4. exchanges ONLY those pairs via ``jax.lax.all_gather`` over the data
     axes (k values + k indices per tensor per worker, vs. d dense values
     for a vanilla all-reduce) — as raw arrays, or bit-packed into a
     single uint32 wire buffer per tensor when ``SyncConfig.wire ==
     "packed"`` (see ``repro.core.encoding`` and DESIGN.md),
  5. scatter-adds the W*k received pairs into a dense update and divides
     by W,
  6. keeps m_w' = u_w - own_selection.

Row-block top-k (TPU adaptation of the paper's top_k)
-----------------------------------------------------
A global top-k over a tensor-parallel parameter would require gathering
the full tensor across model shards first. Instead we select the top-k_row
within each ROW, where rows run over all axes EXCEPT a chosen ``col_axis``
that is NOT model-sharded (the launch layer picks it from the sharding
rules). Every row then lives entirely inside one model shard: selection is
shard-local, the (values, indices) arrays inherit the model sharding, and
the data-axis all-gather never touches the model axis. Row-block top-k is
a k-contraction (per-row top-k dominates per-row rand-k, which equals
rand_k in expectation; cf. ``repro.core.compression.blockwise_top_k``), so
Theorem 2.4 applies unchanged.

Sync strategies
---------------
* ``sparse_allgather`` — single-stage gather over all data axes (paper).
* ``hierarchical``     — beyond-paper: gather + densify + RE-COMPRESS
  within the pod, then gather the re-compressed summary across pods. The
  inter-pod bytes drop from W_pod*k to k_pod; the re-compression residual
  is folded back into the local memory, preserving the error-feedback
  guarantee (composition of contractions with feedback is again a
  contraction with feedback). On the bucketed path this is a true
  two-level scheme: each bucket re-selects the intra-pod mean at its OWN
  pod k (``SyncConfig.pod_ratios``, autotuned by ``autotune_pod_ratios``
  from the bucket's realized mass capture), and
  ``bucketed_message_bytes(by_level=True)`` accounts the intra- vs
  cross-pod bytes exactly per level.
* ``dense``            — vanilla data-parallel all-reduce baseline.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import pipeline
from repro.core.pipeline import COMM, COMPUTE, QUANT, REPACK

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PodConfig:
    """The two-level (pod-aware) half of a ``SyncConfig``."""

    # hierarchical only: re-compression ratio for the intra-pod mean
    ratio: Optional[float] = None
    # hierarchical + bucketed: per-bucket pod re-compression ratios
    # (index-aligned with BucketPlan.buckets), overriding the global
    # ``ratio`` bucket by bucket. Produced by ``autotune_pod_ratios``
    # from each bucket's realized mass capture so attention-sized and
    # bias-sized buckets don't share one k.
    ratios: Optional[Tuple[float, ...]] = None
    # mass-capture target the autotuner sizes each bucket's pod k for:
    # the smallest k whose top-k captures this fraction of the bucket's
    # per-row squared mass (clamped to the pod mean's support bound).
    mass_target: float = 0.9
    # Runtime pod k (bucketed hierarchical only): shape every buffer,
    # wire message and all-gather at the static per-bucket
    # ``pod_k_max_for_bucket`` while the LIVE k arrives as a traced
    # ``pod_ks`` argument to ``bucketed_sync_gradients`` — slots past
    # the live k are masked to (-0.0, 0) no-ops (-0.0 is the additive
    # identity; see ``kernels.topk_select.mask_live_k``) and the live
    # count rides in the packed header (``encoding.LIVE_N_WORD``). This is what
    # lets ``autotune_pod_ratios`` re-calibrate mid-run with ZERO
    # recompiles (see launch.train ``--pod-refresh-every``).
    dynamic: bool = False
    # optional cap (fraction of cols) on the static padded pod k —
    # bounds the gathered buffer below the full n_data*k_row support
    # bound at the cost of clamping how far a refresh can raise k.
    k_max_ratio: Optional[float] = None
    # the mesh axis pods are laid out over (set on multi-pod meshes)
    axis: Optional[str] = None

    def __post_init__(self):
        if self.ratios is not None and not isinstance(self.ratios, tuple):
            object.__setattr__(self, "ratios", tuple(self.ratios))


@dataclasses.dataclass(frozen=True)
class WireConfig:
    """What one sync message looks like on the wire."""

    # Wire format for the all-gather (repro.core.encoding):
    #  * "unpacked": separate (value_dtype values, int32 indices) arrays —
    #    k * (value_bits + 32) bits per row.
    #  * "packed": one uint32 buffer per leaf/bucket with bf16/f32 values
    #    and ceil(log2 cols)-bit row-local indices — k * (value_bits +
    #    ceil(log2 cols)) bits per row plus header/alignment slack. The
    #    decode + scatter-add runs shard-locally after the gather; results
    #    are bit-identical to the unpacked path. NB: on model-sharded
    #    leaves the encode's (rows, k) reshape can force GSPMD gathers —
    #    the bucketed path (already model-axis-free) is the primary user.
    wire: str = "unpacked"
    value_dtype: str = "float32"
    # QSGD-style stochastic quantization of the selected values to
    # ``quant`` levels (Qsparse-local-SGD's Q step): every sync stage
    # quantizes BEFORE its encode, the sender's own contribution uses
    # the DEQUANTIZED values so the error-feedback memory absorbs the
    # quantization error, and the packed wire ships
    # ``1 + ceil(log2(quant+1))``-bit codes plus one f32 row norm (see
    # ``encoding.WireSpec(quant=...)``). ``None`` = exact values.
    quant: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class TransportConfig:
    """How sync messages move and are budgeted across the slow links."""

    # Header-aware repack transport (bucketed hierarchical + pod dynamic):
    # grow each bucket's stage chain an explicit R stage between the pod
    # re-select/encode and the cross-pod gather — the point where a
    # header-aware transport compacts the k_max-padded summary down to
    # its live payload (``encoding.repack``) so cross-pod bytes track
    # the LIVE k, not the pad. In-jit the R stage is the identity
    # (static shapes cannot shrink inside a trace — results stay
    # BITWISE identical with repack on/off, and across overlap modes);
    # the byte reduction is realized by the host/pod-boundary transport
    # (``repack_transport``) and accounted by
    # ``bucketed_message_bytes(..., pod_ks=...)``.
    repack: bool = False
    # Global cross-pod byte budget per step per worker (bytes). Consumed
    # by ``core.budget.BudgetController``: instead of sizing each
    # bucket's pod k for a mass-capture target, the controller
    # water-fills this budget across buckets by marginal
    # mass-per-byte. ``None`` keeps the mass-target sizing.
    byte_budget: Optional[int] = None
    # Software-pipelined bucket schedule (repro.core.pipeline):
    #  * None  — legacy bucket-after-bucket emission (no barriers).
    #  * False — strict sequential schedule, pinned with barriers
    #            (depth 1: the honest overlap-off baseline).
    #  * True  — double buffer (depth 2): bucket b's all-gather +
    #            decode overlaps bucket b+1's top-k select + encode.
    # All three modes apply BITWISE-identical params and memory: the
    # pipeline only reorders stage emission and adds
    # ``optimization_barrier`` edges, never a value-changing op.
    overlap: Optional[bool] = None


# legacy flat SyncConfig keyword -> (grouped field, name inside the group)
_FLAT_TO_GROUP = {
    "pod_ratio": ("pod", "ratio"),
    "pod_ratios": ("pod", "ratios"),
    "pod_mass_target": ("pod", "mass_target"),
    "pod_dynamic": ("pod", "dynamic"),
    "pod_k_max_ratio": ("pod", "k_max_ratio"),
    "pod_axis": ("pod", "axis"),
    "wire": ("wire_cfg", "wire"),
    "value_dtype": ("wire_cfg", "value_dtype"),
    "quant": ("wire_cfg", "quant"),
    "repack": ("transport", "repack"),
    "byte_budget": ("transport", "byte_budget"),
    "overlap": ("transport", "overlap"),
}

# known-good flag bundles (see SyncConfig.preset)
_PRESETS = {
    # vanilla data-parallel all-reduce baseline
    "dense": dict(strategy="dense"),
    # the paper's Mem-SGD: bucketed top-k over the packed wire
    "topk": dict(strategy="sparse_allgather", bucketed=True,
                 wire_cfg=WireConfig(wire="packed")),
    # Qsparse-local-SGD: H local steps, top-k + s-level quantization,
    # one shared error memory (Basu et al.)
    "qsparse_local": dict(strategy="sparse_allgather", bucketed=True,
                          local_steps=4,
                          wire_cfg=WireConfig(wire="packed", quant=15)),
    # two-level pod sync with runtime pod k, header-aware repack
    # transport and the byte-budget water-filler ready to take a budget
    "pod_budgeted": dict(strategy="hierarchical", bucketed=True,
                         wire_cfg=WireConfig(wire="packed"),
                         pod=PodConfig(dynamic=True),
                         transport=TransportConfig(repack=True)),
}


@dataclasses.dataclass(frozen=True, init=False)
class SyncConfig:
    """How gradients are synchronized across the data-parallel axes.

    Grouped API: the pod-hierarchy knobs live in ``cfg.pod``
    (:class:`PodConfig`), the message format in ``cfg.wire_cfg``
    (:class:`WireConfig`) and the transport/scheduling knobs in
    ``cfg.transport`` (:class:`TransportConfig`)::

        SyncConfig(strategy="hierarchical", bucketed=True,
                   pod=PodConfig(dynamic=True, axis="pod"),
                   wire=WireConfig(wire="packed"),
                   transport=TransportConfig(repack=True))

    or start from a known-good bundle: ``SyncConfig.preset("topk")``.
    Flat reads (``cfg.pod_dynamic``, ``cfg.wire``, ``cfg.repack``, ...)
    keep working as properties. Flat CONSTRUCTION keywords
    (``SyncConfig(pod_dynamic=True, wire="packed")``) still parse via a
    deprecation shim — one release of warning before removal; use the
    grouped form or ``preset(...)``. Cross-flag constraints are enforced
    by ``validate()``, called at every sync entry point.
    """

    strategy: str = "sparse_allgather"  # | "hierarchical" | "dense"
    ratio: float = 0.001  # per-row k_row = max(k_min, ratio * row_len)
    k_min: int = 1
    k_max: Optional[int] = None
    # Qsparse-local-SGD local steps: workers take H uncommunicated steps
    # accumulating u = sum_h eta_h * g_h in bucket space, then sync ONCE
    # through the top-k (+ quantize) wire path — cross-worker bytes per
    # step drop by ~H while the shared memory absorbs every residual.
    # H=1 is the per-step paper path, bit-for-bit (the driver keeps it
    # on the literal unaccumulated code path).
    local_steps: int = 1
    data_axes: Tuple[str, ...] = ("data",)
    # leaves smaller than this sync densely (norm scales, biases): the
    # index overhead would exceed the dense message.
    dense_below: int = 16_384
    # Row layout for the selection:
    #  * "flatten": moveaxis + reshape to (R, C). Simple, but merging an
    #    unsharded leading dim with a model-sharded dim is a reshape GSPMD
    #    cannot repartition -> involuntary full-tensor all-gathers
    #    (measured in EXPERIMENTS.md §Perf iteration 1).
    #  * "batched": keep the native rank; top-k/scatter run batched over
    #    the leading dims, so every op preserves the tensor's sharding.
    layout: str = "batched"
    # pin sync intermediates to the parameter's sharding (A2 experiment;
    # measured no-op — GSPMD's sort/scatter partitioners replicate anyway)
    constrain_intermediates: bool = False
    # Selection/densify implementation:
    #  * "topk_scatter": jax.lax.top_k + batched scatter-add. XLA's SPMD
    #    partitioner REPLICATES both sort and scatter across the model
    #    axis (full-tensor all-gather/all-reduce per leaf — measured in
    #    §Perf iteration A3's microbenchmarks).
    #  * "argmax_onehot": k iterations of masked row-argmax + one-hot
    #    einsum densify — every op partitions cleanly; costs an extra
    #    O(k * size) elementwise flops (negligible for k <= 64).
    #  * "threshold_onehot": single-pass bisection threshold select
    #    (O(32*C), k-independent — repro.kernels.topk_select) + one-hot
    #    densify. Partitions cleanly like argmax_onehot but with no k
    #    limit; tiny k (<= the backend's measured cutover,
    #    repro.utils.platform.topk_loop_cutover) falls back to the
    #    argmax loop.
    selection: str = "argmax_onehot"
    argmax_k_limit: int = 64  # fall back to top_k beyond this
    # Bucketed flat-buffer engine (repro.core.buckets): pack the pytree
    # into a few dtype-homogeneous (R, bucket_cols) buffers so the sync
    # runs over <= ~4 big tensors instead of one dispatch per leaf.
    bucketed: bool = False
    bucket_cols: int = 1024
    pod: PodConfig = PodConfig()
    wire_cfg: WireConfig = WireConfig()
    transport: TransportConfig = TransportConfig()

    def __init__(
        self,
        strategy: str = "sparse_allgather",
        ratio: float = 0.001,
        k_min: int = 1,
        k_max: Optional[int] = None,
        local_steps: int = 1,
        data_axes: Tuple[str, ...] = ("data",),
        dense_below: int = 16_384,
        layout: str = "batched",
        constrain_intermediates: bool = False,
        selection: str = "argmax_onehot",
        argmax_k_limit: int = 64,
        bucketed: bool = False,
        bucket_cols: int = 1024,
        pod: Optional[PodConfig] = None,
        wire: Union[WireConfig, str, None] = None,
        transport: Optional[TransportConfig] = None,
        wire_cfg: Optional[WireConfig] = None,
        _warn: bool = True,
        **legacy,
    ):
        # ``wire=`` doubles as the grouped keyword (a WireConfig) and
        # the legacy flat format string ("packed"/"unpacked");
        # ``wire_cfg=`` is the unambiguous field name (what
        # dataclasses.replace round-trips).
        if isinstance(wire, WireConfig):
            if wire_cfg is not None:
                raise TypeError(
                    "pass either wire=WireConfig(...) or wire_cfg=, not both"
                )
            wire_cfg = wire
            wire = None
        if wire is not None:
            legacy["wire"] = wire
        pod = pod if pod is not None else PodConfig()
        wire_cfg = wire_cfg if wire_cfg is not None else WireConfig()
        transport = transport if transport is not None else TransportConfig()
        unknown = set(legacy) - set(_FLAT_TO_GROUP)
        if unknown:
            raise TypeError(
                f"SyncConfig got unexpected argument(s) {sorted(unknown)}"
            )
        if legacy and _warn:
            warnings.warn(
                "flat SyncConfig keyword(s) "
                f"{sorted(legacy)} are deprecated; use the grouped "
                "pod=PodConfig(...)/wire=WireConfig(...)/"
                "transport=TransportConfig(...) fields or "
                "SyncConfig.preset(...) — the flat shim is kept for one "
                "release",
                DeprecationWarning,
                stacklevel=2,
            )
        groups = {"pod": pod, "wire_cfg": wire_cfg, "transport": transport}
        over: dict = {"pod": {}, "wire_cfg": {}, "transport": {}}
        for k, v in legacy.items():
            grp, name = _FLAT_TO_GROUP[k]
            over[grp][name] = v
        for grp, kw in over.items():
            if kw:
                groups[grp] = dataclasses.replace(groups[grp], **kw)
        set_ = object.__setattr__
        set_(self, "strategy", strategy)
        set_(self, "ratio", ratio)
        set_(self, "k_min", k_min)
        set_(self, "k_max", k_max)
        set_(self, "local_steps", int(local_steps))
        set_(self, "data_axes", tuple(data_axes))
        set_(self, "dense_below", dense_below)
        set_(self, "layout", layout)
        set_(self, "constrain_intermediates", constrain_intermediates)
        set_(self, "selection", selection)
        set_(self, "argmax_k_limit", argmax_k_limit)
        set_(self, "bucketed", bucketed)
        set_(self, "bucket_cols", bucket_cols)
        set_(self, "pod", groups["pod"])
        set_(self, "wire_cfg", groups["wire_cfg"])
        set_(self, "transport", groups["transport"])

    # -- flat reads (back-compat with the pre-grouping field names) ---------

    @property
    def pod_ratio(self) -> Optional[float]:
        return self.pod.ratio

    @property
    def pod_ratios(self) -> Optional[Tuple[float, ...]]:
        return self.pod.ratios

    @property
    def pod_mass_target(self) -> float:
        return self.pod.mass_target

    @property
    def pod_dynamic(self) -> bool:
        return self.pod.dynamic

    @property
    def pod_k_max_ratio(self) -> Optional[float]:
        return self.pod.k_max_ratio

    @property
    def pod_axis(self) -> Optional[str]:
        return self.pod.axis

    @property
    def wire(self) -> str:
        return self.wire_cfg.wire

    @property
    def value_dtype(self) -> str:
        return self.wire_cfg.value_dtype

    @property
    def quant(self) -> Optional[int]:
        return self.wire_cfg.quant

    @property
    def repack(self) -> bool:
        return self.transport.repack

    @property
    def byte_budget(self) -> Optional[int]:
        return self.transport.byte_budget

    @property
    def overlap(self) -> Optional[bool]:
        return self.transport.overlap

    # -- warning-free grouped edits -----------------------------------------

    def with_pod(self, **kw) -> "SyncConfig":
        """Replace fields of ``self.pod`` (grouped, warning-free)."""
        return dataclasses.replace(
            self, pod=dataclasses.replace(self.pod, **kw))

    def with_wire(self, **kw) -> "SyncConfig":
        """Replace fields of ``self.wire_cfg`` (grouped, warning-free)."""
        return dataclasses.replace(
            self, wire_cfg=dataclasses.replace(self.wire_cfg, **kw))

    def with_transport(self, **kw) -> "SyncConfig":
        """Replace fields of ``self.transport`` (grouped, warning-free)."""
        return dataclasses.replace(
            self, transport=dataclasses.replace(self.transport, **kw))

    # -- presets ------------------------------------------------------------

    @classmethod
    def preset(cls, name: str, **overrides) -> "SyncConfig":
        """A known-good flag bundle, editable via ``overrides`` (grouped
        keywords replace a whole sub-config; flat keywords edit single
        fields on top of the bundle, warning-free — presets ARE the
        blessed construction path):

        * ``"dense"``         — vanilla data-parallel all-reduce.
        * ``"topk"``          — bucketed Mem-SGD over the packed wire.
        * ``"qsparse_local"`` — Qsparse-local-SGD: 4 local steps, top-k
          + 15-level stochastic quantization, packed wire.
        * ``"pod_budgeted"``  — two-level pod sync, runtime pod k,
          header-aware repack transport (give it ``byte_budget=...`` to
          engage the water-filler; pod_axis is filled in by the
          launcher from the mesh).
        """
        try:
            merged = dict(_PRESETS[name])
        except KeyError:
            raise ValueError(
                f"unknown SyncConfig preset {name!r}; available: "
                f"{sorted(_PRESETS)}"
            ) from None
        for k, v in overrides.items():
            if k == "wire" and isinstance(v, WireConfig):
                merged["wire_cfg"] = v
            else:
                merged[k] = v
        return cls(_warn=False, **merged)

    # -- cross-flag validation ----------------------------------------------

    def validate(self, plan=None) -> "SyncConfig":
        """Check cross-flag consistency; called at every sync entry
        point. Raises a named ``ValueError`` for each documented illegal
        combination instead of silently mis-syncing. Pass the
        ``BucketPlan`` when available to also check per-bucket
        alignment. Returns ``self`` so call sites can chain."""
        if self.strategy not in ("sparse_allgather", "hierarchical", "dense"):
            raise ValueError(f"unknown sync strategy {self.strategy!r}")
        if self.local_steps < 1:
            raise ValueError(
                f"SyncConfig.local_steps must be >= 1, got {self.local_steps}"
            )
        if self.local_steps > 1 and not self.bucketed:
            raise ValueError(
                "SyncConfig.local_steps > 1 requires the bucketed engine "
                "(bucketed=True): the local-step accumulator lives in "
                "bucket space"
            )
        if self.quant is not None:
            if self.quant < 1:
                raise ValueError(
                    f"WireConfig.quant must be >= 1 levels, got {self.quant}"
                )
            if self.strategy == "dense":
                raise ValueError(
                    "WireConfig.quant composes with the sparse selections; "
                    "the dense all-reduce strategy has no quantize stage"
                )
            if not self.bucketed:
                raise ValueError(
                    "WireConfig.quant requires the bucketed engine "
                    "(bucketed=True): quantization is defined on the "
                    "(rows, cols) bucket layout"
                )
            if self.value_dtype != "float32":
                raise ValueError(
                    "WireConfig.quant replaces the value dtype on the wire "
                    "(codes + f32 row norms); combining it with "
                    f"value_dtype={self.value_dtype!r} would quantize "
                    "already-rounded values"
                )
        if self.pod.dynamic and (
            self.strategy != "hierarchical" or self.pod.axis is None
            or not self.bucketed
        ):
            raise ValueError(
                "PodConfig.dynamic (runtime pod k) requires the bucketed "
                "hierarchical strategy with a pod axis — this config "
                "would silently ignore the live k schedule"
            )
        if self.transport.repack and not self.pod.dynamic:
            raise ValueError(
                "TransportConfig.repack requires PodConfig.dynamic: the "
                "repack boundary compacts the k_max-padded pod summary, "
                "which only exists on the runtime-k path"
            )
        if self.transport.byte_budget is not None and (
            self.strategy != "hierarchical" or not self.bucketed
        ):
            raise ValueError(
                "TransportConfig.byte_budget requires the bucketed "
                "hierarchical strategy: the budget water-fills per-bucket "
                "pod ks across a BucketPlan"
            )
        if plan is not None:
            validate_pod_ratios(self, plan)
        return self

    def overlap_depth(self) -> Optional[int]:
        """Pipeline depth the sync schedules at (None/1/2 — see
        ``overlap`` and ``repro.core.pipeline``)."""
        return pipeline.overlap_depth(self.overlap)

    def k_for(self, row_len: int) -> int:
        k = max(self.k_min, int(round(self.ratio * row_len)))
        if self.k_max is not None:
            k = min(k, self.k_max)
        return min(k, row_len)

    def pod_k_for(self, row_len: int) -> int:
        r = self.pod_ratio if self.pod_ratio is not None else self.ratio
        k = max(self.k_min, int(round(r * row_len)))
        if self.k_max is not None:
            k = min(k, self.k_max)
        return min(k, row_len)

    def pod_k_for_bucket(self, bucket: int, row_len: int) -> int:
        """Pod-stage k for one bucket: the autotuned per-bucket ratio
        when ``pod_ratios`` is set, the global ``pod_ratio`` otherwise.

        An out-of-range bucket index RAISES: ``pod_ratios`` must be
        index-aligned with the bucket plan (``validate_pod_ratios``) —
        the old silent fallback to the global ratio quietly desynced the
        byte accounting from the wire layout."""
        if self.pod_ratios is None:
            return self.pod_k_for(row_len)
        if bucket >= len(self.pod_ratios):
            raise ValueError(
                f"SyncConfig.pod_ratios has {len(self.pod_ratios)} entries "
                f"but bucket {bucket} was requested — pod_ratios must be "
                "index-aligned with the BucketPlan (one ratio per bucket; "
                "regenerate with autotune_pod_ratios)"
            )
        k = max(self.k_min, int(round(self.pod_ratios[bucket] * row_len)))
        if self.k_max is not None:
            k = min(k, self.k_max)
        return min(k, row_len)

    def pod_k_max_for_bucket(self, bucket: int, row_len: int,
                             n_data: int) -> int:
        """Static ceiling for one bucket's pod-stage k — the size the
        dynamic (k-padded) path shapes its buffers/wire at, and the
        support bound the delta spec must honour so a live ratio
        refresh can never overflow it. Covers the pod mean's support
        bound (``n_data * k_row`` — the most entries the pod stage can
        see), optionally tightened by ``pod_k_max_ratio``, and never
        below the statically configured pod k."""
        cap = min(row_len, max(1, n_data * self.k_for(row_len)))
        if self.pod_k_max_ratio is not None:
            cap = min(cap, max(self.k_min,
                               int(round(self.pod_k_max_ratio * row_len))))
        return min(row_len, max(cap, self.pod_k_for_bucket(bucket, row_len)))


def validate_pod_ratios(cfg: SyncConfig, plan) -> None:
    """Raise when ``cfg.pod_ratios`` is not index-aligned with ``plan``
    — a shorter tuple used to fall back silently to the global
    ``pod_ratio`` for the tail buckets, desyncing the byte accounting
    (and the delta-spec support bound) from what the wire ships."""
    if cfg.pod_ratios is None:
        return
    if len(cfg.pod_ratios) != len(plan.buckets):
        raise ValueError(
            f"SyncConfig.pod_ratios has {len(cfg.pod_ratios)} entries for "
            f"a {len(plan.buckets)}-bucket plan — regenerate them with "
            "autotune_pod_ratios (one ratio per bucket, dense buckets "
            "included)"
        )


def _axis_size(axis_names: Sequence[str]) -> int:
    n = 1
    for a in axis_names:
        n = n * jax.lax.axis_size(a)
    return n


def _to_rows(x: Array, col_axis: int) -> Tuple[Array, tuple]:
    """Move col_axis last and flatten the rest: (R, C)."""
    moved = jnp.moveaxis(x, col_axis, -1)
    shape = moved.shape
    return moved.reshape(-1, shape[-1]), shape


def _from_rows(rows: Array, moved_shape: tuple, col_axis: int) -> Array:
    return jnp.moveaxis(rows.reshape(moved_shape), -1, col_axis)


def _row_topk(u: Array, k: int, constrain=lambda x: x) -> Tuple[Array, Array]:
    """u: (..., C) -> (vals (..., k), idx (..., k) int32) by |.| per row.
    Batched over all leading dims (sharding-preserving)."""
    _, idx = jax.lax.top_k(jnp.abs(u), k)
    idx = constrain(idx.astype(jnp.int32))
    vals = constrain(jnp.take_along_axis(u, idx, axis=-1))
    return vals, idx


def _row_topk_argmax(u: Array, k: int, constrain=lambda x: x
                     ) -> Tuple[Array, Array]:
    """Partition-safe per-row top-k: k masked-argmax iterations (no sort;
    GSPMD keeps batch-dim sharding). Ties resolve to the lowest index —
    identical semantics to the Pallas kernel and its oracle."""
    absu = jnp.abs(u.astype(jnp.float32))
    iota = jax.lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)
    vals = jnp.zeros(u.shape[:-1] + (k,), u.dtype)
    idxs = jnp.zeros(u.shape[:-1] + (k,), jnp.int32)
    for t in range(k):
        j = jnp.argmax(absu, axis=-1).astype(jnp.int32)
        v = jnp.take_along_axis(u, j[..., None], axis=-1)[..., 0]
        vals = vals.at[..., t].set(v)
        idxs = idxs.at[..., t].set(j)
        absu = jnp.where(iota == j[..., None], -jnp.inf, absu)
    return vals, idxs


def _row_topk_threshold(u: Array, k: int, constrain=lambda x: x
                        ) -> Tuple[Array, Array]:
    """Partition-safe single-pass per-row top-k: exact bit-bisection
    threshold (O(32*C) compare+count sweeps, k-independent — vs the
    argmax loop's O(k*C) dependent passes) + binary-search compaction
    (gathers along the unsharded row axis only; no sort, no scatter, so
    GSPMD keeps the batch sharding). Output contract identical to
    ``_row_topk_argmax`` / the Pallas kernels: decreasing |.|, ties to
    the lowest index."""
    from repro.kernels.topk_select import threshold_topk_jnp

    vals, idx = threshold_topk_jnp(u, k)
    return constrain(vals), constrain(idx.astype(jnp.int32))


def _row_densify_onehot(shape: tuple, vals: Array, idx: Array, dtype,
                        constrain=lambda x: x) -> Array:
    """Partition-safe densify: one-hot einsum instead of scatter (XLA's
    scatter partitioner replicates across the model axis)."""
    C = shape[-1]
    iota = jnp.arange(C, dtype=jnp.int32)
    onehot = (idx[..., None] == iota).astype(dtype)  # (..., k', C)
    return constrain(
        jnp.einsum("...kc,...k->...c", onehot, vals.astype(dtype))
    )


def _batch_iotas(shape: tuple) -> tuple:
    """Broadcastable index grids for every dim except the last."""
    nd = len(shape)
    out = []
    for i, s in enumerate(shape[:-1]):
        rshape = [1] * nd
        rshape[i] = s
        out.append(jnp.arange(s, dtype=jnp.int32).reshape(rshape))
    return tuple(out)


def _row_scatter(shape: tuple, vals: Array, idx: Array, dtype,
                 constrain=lambda x: x) -> Array:
    """Scatter-add (..., k') pairs into a dense (..., C) along the last
    axis, batched over leading dims (sharding-preserving)."""
    out = jnp.zeros(shape, dtype)
    return constrain(out.at[(*_batch_iotas(shape), idx)].add(vals))


def _pick_selection(cfg: "SyncConfig", k_row: int):
    """(topk, densify) implementations for one leaf/bucket (see the
    SyncConfig.selection comment for the trade-offs)."""
    from repro.utils.platform import topk_loop_cutover

    if cfg.selection not in (
        "topk_scatter", "argmax_onehot", "threshold_onehot"
    ):
        raise ValueError(f"unknown SyncConfig.selection {cfg.selection!r}")
    if cfg.selection == "threshold_onehot":
        if k_row <= topk_loop_cutover():
            return _row_topk_argmax, _row_densify_onehot
        return _row_topk_threshold, _row_densify_onehot
    if cfg.selection == "argmax_onehot" and k_row <= cfg.argmax_k_limit:
        return _row_topk_argmax, _row_densify_onehot
    return _row_topk, _row_scatter


def _gather_pairs(vals, idx, axes):
    """all_gather over every data axis; concatenated along the last axis:
    (..., W*k)."""
    for ax in axes:
        vals = jax.lax.all_gather(vals, ax, axis=vals.ndim - 1, tiled=True)
        idx = jax.lax.all_gather(idx, ax, axis=idx.ndim - 1, tiled=True)
    return vals, idx


def _encode_packed(vals, idx, wspec, live_n=None):
    """Encode (vals, idx) into one uint32 wire buffer
    (repro.core.encoding). ``live_n`` stamps a runtime live-slot count
    into the k-padded message's header (the pairs past it must already
    be masked). Pure compute — the pipeline's E stage."""
    from repro.core import encoding as enc

    k = wspec.k
    return enc.encode(
        wspec, vals.reshape(-1, k), idx.reshape(-1, k).astype(jnp.int32),
        live_n=live_n,
    )


def _gather_buf(buf, axes):
    """all-gather a wire buffer over every data axis (tiled along axis
    0). Pure communication — the pipeline's G stage."""
    for ax in axes:
        buf = jax.lax.all_gather(buf, ax, axis=0, tiled=True)
    return buf


def _decode_packed(buf, wspec, axes, lead_shape):
    """Decode a gathered wire buffer shard-locally back to (..., W*k)
    pairs, in exactly the tile order ``_gather_pairs`` produces, so the
    downstream densify/mean is bit-identical to the unpacked path. Pure
    compute — part of the pipeline's D stage."""
    from repro.core import encoding as enc

    W = _axis_size(axes)
    gv, gi = jax.vmap(lambda b: enc.decode(wspec, b))(
        buf.reshape(W, wspec.words)
    )
    gv = jnp.moveaxis(gv, 0, 1).reshape(tuple(lead_shape) + (W * wspec.k,))
    gi = jnp.moveaxis(gi, 0, 1).reshape(tuple(lead_shape) + (W * wspec.k,))
    return gv, gi


def _gather_packed(vals, idx, axes, wspec, live_n=None):
    """Packed-wire gather: encode -> all-gather -> decode (the three
    helpers above, run back to back for the non-pipelined callers)."""
    buf = _gather_buf(_encode_packed(vals, idx, wspec, live_n), axes)
    return _decode_packed(buf, wspec, axes, vals.shape[:-1])


def _run_stages(init, stages):
    st = init
    for f in stages:
        st = f(st)
    return st


def _fold_axes(key, axes):
    """Fold each named axis' index into a PRNG key: folding every data
    axis makes the key worker-unique (decorrelated level-1 quantization
    noise); folding only the pod axis keeps it identical WITHIN a pod —
    required where every worker in a pod must quantize the shared pod
    mean to the same codes."""
    for ax in axes:
        key = jax.random.fold_in(key, jax.lax.axis_index(ax))
    return key


def _quantize_selected(vals, idx, s, key):
    """QSGD-quantize a (..., k) selection: returns (norms, codes,
    dequantized f32 values). The dequantized values are what the sender
    densifies as its OWN contribution — bit-identical to what every
    receiver decodes (``encoding.dequantize_rows`` is the single shared
    formula), so the error-feedback memory absorbs exactly the
    quantization error that ships."""
    from repro.core import encoding as enc
    from repro.optim.qsgd import quantize_rows

    norms, codes = quantize_rows(vals.astype(jnp.float32), s, key)
    return norms, codes, enc.dequantize_rows(norms, codes, s)


def _encode_quant(wspec, codes, idx, norms, live_n=None):
    from repro.core import encoding as enc

    k = wspec.k
    return enc.encode(
        wspec, codes.reshape(-1, k), idx.reshape(-1, k).astype(jnp.int32),
        live_n=live_n, norms=norms.reshape(-1),
    )


def _sparse_stages(shape, dtype, k_row, axes, value_dtype,
                   constrain=lambda x: x, topk=_row_topk,
                   densify=None, wire: str = "unpacked",
                   quant: Optional[int] = None, qkey=None):
    """Stage chain for one flat sparse leaf/bucket, decomposed for the
    bucket pipeline (repro.core.pipeline):

      E (compute): top-k select + own densify + wire encode
      Q (quant):   OPTIONAL (``quant=s``) — stochastic s-level
                   quantization of the selected values (worker-unique
                   key: ``qkey`` folded over every gather axis). The own
                   densify moves here and uses the DEQUANTIZED values.
      G (comm):    all-gather over the data axes
      D (compute): wire decode + densify + mean

    Returns ``(stages, kinds, nbytes)``; stage 0 takes ``u`` (..., C)
    and the final stage returns ``(mean update, own selection)``. Run
    back to back, the no-quant stages compute EXACTLY the op sequence
    the old monolithic ``_leaf_sparse_sync`` emitted."""
    densify = densify or _row_scatter
    rows = 1
    for s in shape[:-1]:
        rows *= s
    W = _axis_size(axes)
    if wire == "packed":
        from repro.core import encoding as enc

        wspec = enc.WireSpec(rows=rows, cols=shape[-1], k=k_row,
                             value_dtype=jnp.dtype(value_dtype).name,
                             quant=quant)
        nbytes = wspec.nbytes
    else:
        wspec = None
        nbytes = rows * k_row * (jnp.dtype(value_dtype).itemsize + 4)

    def select_encode(u):
        vals, idx = topk(u, k_row, constrain)
        own = densify(shape, vals, idx, dtype, constrain)
        if wspec is not None:
            payload = _encode_packed(vals.astype(value_dtype), idx, wspec)
        else:
            payload = (vals.astype(value_dtype), idx)
        return own, payload

    def select(u):
        return topk(u, k_row, constrain)

    def quantize_encode(st):
        vals, idx = st
        key = _fold_axes(jax.random.fold_in(qkey, 1), axes)
        norms, codes, deq = _quantize_selected(vals, idx, quant, key)
        own = densify(shape, deq, idx, dtype, constrain)
        if wspec is not None:
            payload = _encode_quant(wspec, codes, idx, norms)
        else:
            payload = (deq.astype(value_dtype), idx)
        return own, payload

    def gather(st):
        own, payload = st
        if wspec is not None:
            return own, _gather_buf(payload, axes)
        return own, _gather_pairs(*payload, axes)

    def decode_apply(st):
        own, payload = st
        if wspec is not None:
            gv, gi = _decode_packed(payload, wspec, axes, shape[:-1])
        else:
            gv, gi = payload
        gv, gi = constrain(gv), constrain(gi)
        update = (densify(shape, gv, gi, value_dtype, constrain)
                  / W).astype(dtype)
        return update, own

    if quant is not None:
        if qkey is None:
            raise ValueError(
                "quantized sparse stages need a qkey (threaded PRNG key)")
        return ([select, quantize_encode, gather, decode_apply],
                (COMPUTE, QUANT, COMM, COMPUTE), nbytes)
    return ([select_encode, gather, decode_apply],
            (COMPUTE, COMM, COMPUTE), nbytes)


def _leaf_sparse_sync(u: Array, k_row: int, axes, value_dtype,
                      constrain=lambda x: x, topk=_row_topk,
                      densify=None, wire: str = "unpacked"):
    """u: (..., C). Returns (mean update, own selection, bytes/worker)."""
    stages, _, nbytes = _sparse_stages(
        u.shape, u.dtype, k_row, axes, value_dtype, constrain, topk,
        densify, wire,
    )
    update, own = _run_stages(u, stages)
    return update, own, nbytes


def _leaf_hierarchical_sync(u, k_row, k_pod, data_axes, pod_axis, value_dtype,
                            constrain=lambda x: x, topk=_row_topk,
                            densify=None, wire: str = "unpacked",
                            k_pod_live=None):
    """Two-level scheme: worker selections gather intra-pod at ``k_row``,
    the intra-pod mean is re-selected at ``k_pod`` and only that summary
    crosses the pod boundary; the pod-level residual is returned for the
    caller to fold into error-feedback memory (mass conservation:
    mean_w(u) == update + mean_w(new_memory) holds exactly up to
    float-sum association). Both gather stages go over the packed wire
    when ``wire="packed"``. Returns
    (update, own, residual, (intra_pod_bytes, cross_pod_bytes)).

    ``k_pod_live`` (traced scalar) switches the pod stage to RUNTIME k:
    ``k_pod`` then acts as the static padded ceiling — selection runs at
    ``k_pod`` and the tail slots past ``k_pod_live`` are masked to
    (-0.0, 0) no-ops (``kernels.topk_select.mask_live_k``), so the live k
    can move between steps without changing any shape, and mass
    conservation holds for every live k (pod_sel + residual == pod_mean
    exactly, whatever the selection kept). The reported cross-pod bytes
    are the PADDED gather size — the in-jit cost; a header-aware
    transport ships ``message_nbytes(..., live_k)`` instead."""
    stages, _, level_bytes = _hier_stages(
        u.shape, u.dtype, k_row, k_pod, data_axes, pod_axis, value_dtype,
        constrain, topk, densify, wire, k_pod_live,
    )
    update, own, residual = _run_stages(u, stages)
    return update, own, residual, level_bytes


def _hier_stages(shape, dtype, k_row, k_pod, data_axes, pod_axis,
                 value_dtype, constrain=lambda x: x, topk=_row_topk,
                 densify=None, wire: str = "unpacked", k_pod_live=None,
                 repack_boundary: bool = False,
                 quant: Optional[int] = None, qkey=None):
    """Stage chain for one two-level (hierarchical) leaf/bucket,
    decomposed for the bucket pipeline:

      E1 (compute): worker top-k + own densify + level-1 encode
      Q1 (quant):   OPTIONAL (``quant=s``) — stochastic quantization of
                    the worker selection (worker-unique key); the own
                    densify moves here and uses the DEQUANTIZED values
      G1 (comm):    intra-pod all-gather over the data axes
      M  (compute): level-1 decode + pod mean + pod re-select (live-k
                    mask) + residual + level-2 encode
      Q2 (quant):   OPTIONAL — quantization of the pod summary with a
                    key folded over the POD axis only, so every worker
                    in a pod draws identical codes for the shared pod
                    mean (the residual kept in memory must equal
                    pod_mean - dequantized summary on every worker);
                    the residual computation moves here
      R  (repack):  OPTIONAL (``repack_boundary``) — the header-aware
                    transport's compaction point, right before the slow
                    link. In-jit an identity (static shapes cannot
                    shrink inside a trace; bitwise-invariant by
                    construction); the host transport's R stage does
                    the real ``encoding.repack`` byte shrink.
      G2 (comm):    cross-pod all-gather
      D  (compute): level-2 decode + densify + pod mean

    Returns ``(stages, kinds, level_bytes)``; stage 0 takes ``u`` and
    the final stage returns ``(update, own, residual)``. Without quant
    the op sequence is exactly the old monolithic
    ``_leaf_hierarchical_sync`` body."""
    from repro.core import encoding as enc

    densify = densify or _row_scatter
    rows = 1
    for s in shape[:-1]:
        rows *= s
    cols = shape[-1]
    n_data = _axis_size(data_axes)
    n_pods = jax.lax.axis_size(pod_axis)
    name = jnp.dtype(value_dtype).name
    if wire == "packed":
        w1 = enc.WireSpec(rows=rows, cols=cols, k=k_row, value_dtype=name,
                          quant=quant)
        w2 = enc.WireSpec(rows=rows, cols=cols, k=k_pod, value_dtype=name,
                          quant=quant)
    else:
        w1 = w2 = None
    level_bytes = (
        enc.message_nbytes(rows, cols, k_row, name, wire, quant=quant),
        enc.message_nbytes(rows, cols, k_pod, name, wire, quant=quant),
    )

    def l1_select_encode(u):
        vals, idx = topk(u, k_row, constrain)
        own = densify(shape, vals, idx, dtype, constrain)
        if w1 is not None:
            payload = _encode_packed(vals.astype(value_dtype), idx, w1)
        else:
            payload = (vals.astype(value_dtype), idx)
        return own, payload

    def l1_select(u):
        return topk(u, k_row, constrain)

    def l1_quantize_encode(st):
        vals, idx = st
        key = _fold_axes(jax.random.fold_in(qkey, 1),
                         tuple(data_axes) + (pod_axis,))
        norms, codes, deq = _quantize_selected(vals, idx, quant, key)
        own = densify(shape, deq, idx, dtype, constrain)
        if w1 is not None:
            payload = _encode_quant(w1, codes, idx, norms)
        else:
            payload = (deq.astype(value_dtype), idx)
        return own, payload

    def l1_gather(st):
        own, payload = st
        if w1 is not None:
            return own, _gather_buf(payload, data_axes)
        return own, _gather_pairs(*payload, data_axes)

    def _pod_mean_select(payload):
        if w1 is not None:
            gv, gi = _decode_packed(payload, w1, data_axes, shape[:-1])
        else:
            gv, gi = payload
        pod_mean = densify(shape, gv, gi, value_dtype, constrain) / n_data
        pvals, pidx = topk(pod_mean, k_pod, constrain)
        if k_pod_live is not None:
            from repro.kernels.topk_select import mask_live_k

            pvals, pidx = mask_live_k(pvals, pidx, k_pod_live)
            pvals, pidx = constrain(pvals), constrain(pidx)
        return pod_mean, pvals, pidx

    def pod_reselect_encode(st):
        own, payload = st
        pod_mean, pvals, pidx = _pod_mean_select(payload)
        pod_sel = densify(shape, pvals, pidx, value_dtype, constrain)
        # kept in memory (identical pod-wide)
        residual = pod_mean - pod_sel
        if w2 is not None:
            payload2 = _encode_packed(pvals, pidx, w2, live_n=k_pod_live)
        else:
            payload2 = (pvals, pidx)
        return own, residual, payload2

    def pod_reselect(st):
        own, payload = st
        pod_mean, pvals, pidx = _pod_mean_select(payload)
        return own, pod_mean, pvals, pidx

    def pod_quantize_encode(st):
        own, pod_mean, pvals, pidx = st
        # pod-axis-only fold: identical codes on every worker of a pod
        key = _fold_axes(jax.random.fold_in(qkey, 2), (pod_axis,))
        norms, codes, deq = _quantize_selected(pvals, pidx, quant, key)
        pod_sel = densify(shape, deq, pidx, value_dtype, constrain)
        # memory absorbs selection AND quantization error of the summary
        residual = pod_mean - pod_sel
        if w2 is not None:
            payload2 = _encode_quant(w2, codes, pidx, norms,
                                     live_n=k_pod_live)
        else:
            payload2 = (deq.astype(value_dtype), pidx)
        return own, residual, payload2

    def repack_boundary_stage(st):
        # in-jit identity: the traced buffer keeps its static padded
        # layout (invariant 10's bitwise guarantee is untouched); the
        # host executor substitutes the real ``encoding.repack`` here
        return st

    def l2_gather(st):
        own, residual, payload2 = st
        if w2 is not None:
            return own, residual, _gather_buf(payload2, (pod_axis,))
        return own, residual, _gather_pairs(*payload2, (pod_axis,))

    def l2_decode_apply(st):
        own, residual, payload2 = st
        if w2 is not None:
            av, ai = _decode_packed(payload2, w2, (pod_axis,), shape[:-1])
        else:
            av, ai = payload2
        update = (densify(shape, av, ai, value_dtype, constrain)
                  / n_pods).astype(dtype)
        return update, own, residual.astype(dtype)

    if quant is not None:
        if qkey is None:
            raise ValueError(
                "quantized hierarchical stages need a qkey (threaded "
                "PRNG key)")
        stages = [l1_select, l1_quantize_encode, l1_gather, pod_reselect,
                  pod_quantize_encode]
        kinds = [COMPUTE, QUANT, COMM, COMPUTE, QUANT]
    else:
        stages = [l1_select_encode, l1_gather, pod_reselect_encode]
        kinds = [COMPUTE, COMM, COMPUTE]
    if repack_boundary:
        stages.append(repack_boundary_stage)
        kinds.append(REPACK)
    stages += [l2_gather, l2_decode_apply]
    kinds += [COMM, COMPUTE]
    return stages, tuple(kinds), level_bytes


def _dense_stages(shape, dtype, axes):
    """Single-stage chain for a dense leaf/bucket: one all-reduce (the
    pipeline treats it as pure comm, free to overlap with sparse
    buckets' compute). Final state is ``(update, own)``; bytes are the
    buffer size."""
    nbytes = 1
    for s in shape:
        nbytes *= s
    nbytes *= jnp.dtype(dtype).itemsize

    def allreduce(u):
        update = jax.lax.pmean(u, axes if len(axes) > 1 else axes[0])
        return update, u

    return [allreduce], (COMM,), nbytes


def _leaf_dense_sync(u: Array, axes):
    update = jax.lax.pmean(u, axes if len(axes) > 1 else axes[0])
    return update, u, u.size * u.dtype.itemsize


def sparse_sync_gradients(
    cfg: SyncConfig,
    memory_tree,
    grad_tree,
    eta: Array,
    col_axes=None,
    specs=None,
    mesh=None,
):
    """Full PARALLEL-MEM-SGD gradient exchange on a pytree.

    Must be called inside a shard_map manual over cfg.data_axes (+ pod
    axis). ``memory_tree`` leaves match ``grad_tree`` shapes (this worker's
    own memory). ``col_axes``: pytree of ints (or None -> last axis),
    choosing the NON-model-sharded axis used as the row-block column; from
    ``repro.launch.sharding.sync_col_axes``.

    Returns (update_tree [SUBTRACT from params], new_memory_tree,
    bytes_per_worker_per_step [python int]).
    """
    cfg.validate()
    value_dtype = jnp.dtype(cfg.value_dtype)
    all_axes = tuple(cfg.data_axes) + (
        (cfg.pod_axis,) if cfg.pod_axis else ()
    )

    def build_leaf(m, g, col_axis, spec):
        """One leaf's pipeline entry: (init, stages, kinds, finish,
        nbytes). ``init`` is the row-layout u; ``finish`` undoes the
        layout and folds the error-feedback memory."""
        u_full = m + eta * g.astype(m.dtype)
        d = u_full.size
        if cfg.strategy == "dense" or d < cfg.dense_below:
            stages, kinds, nbytes = _dense_stages(
                u_full.shape, u_full.dtype, all_axes)

            def finish(st, u_full=u_full):
                upd, own = st
                return upd, u_full - own

            return u_full, stages, kinds, finish, nbytes
        ca = (col_axis if col_axis is not None else u_full.ndim - 1) % u_full.ndim
        if cfg.layout == "flatten":
            u, moved_shape = _to_rows(u_full, ca)
            unrow = lambda x: _from_rows(x, moved_shape, ca)
            constrain = lambda x: x
        else:  # "batched": moveaxis only — every op preserves sharding
            u = jnp.moveaxis(u_full, ca, -1)
            unrow = lambda x: jnp.moveaxis(x, -1, ca)
            if spec is not None and mesh is not None and cfg.constrain_intermediates:
                # pin every (..., C)- and (..., k)-shaped intermediate to
                # the parameter's own (permuted) sharding so GSPMD never
                # falls back to replicating full tensors around the top-k
                # and scatter ops (§Perf iteration A2).
                dims = list(spec)
                dims.append(dims.pop(ca))  # moveaxis(ca, -1)
                from jax.sharding import NamedSharding, PartitionSpec

                full_s = NamedSharding(mesh, PartitionSpec(*dims))
                rows_s = NamedSharding(
                    mesh, PartitionSpec(*dims[:-1], None))

                def constrain(x):
                    s = full_s if x.shape == u.shape else rows_s
                    return jax.lax.with_sharding_constraint(x, s)

                u = constrain(u)
            else:
                constrain = lambda x: x
        C = u.shape[-1]
        topk, densify = _pick_selection(cfg, cfg.k_for(C))
        if cfg.strategy == "hierarchical" and cfg.pod_axis is not None:
            stages, kinds, level_bytes = _hier_stages(
                u.shape, u.dtype, cfg.k_for(C), cfg.pod_k_for(C),
                tuple(cfg.data_axes), cfg.pod_axis, value_dtype,
                constrain, topk, densify, wire=cfg.wire,
                repack_boundary=cfg.repack,
            )
            nbytes = sum(level_bytes)

            def finish(st, u=u, unrow=unrow):
                upd, own, residual = st
                return unrow(upd), unrow((u - own) + residual)
        elif cfg.strategy in ("sparse_allgather", "hierarchical"):
            stages, kinds, nbytes = _sparse_stages(
                u.shape, u.dtype, cfg.k_for(C), all_axes, value_dtype,
                constrain, topk, densify, wire=cfg.wire,
            )

            def finish(st, u=u, unrow=unrow):
                upd, own = st
                return unrow(upd), unrow(u - own)
        else:
            raise ValueError(f"unknown sync strategy {cfg.strategy!r}")
        return u, stages, kinds, finish, nbytes

    leaves_g, treedef = jax.tree.flatten(grad_tree)
    leaves_m = treedef.flatten_up_to(memory_tree)
    if col_axes is None:
        leaves_c = [None] * len(leaves_g)
    else:
        leaves_c = treedef.flatten_up_to(col_axes)
    if specs is None:
        leaves_s = [None] * len(leaves_g)
    else:
        leaves_s = treedef.flatten_up_to(specs)
    inits, stage_lists, kind_lists, finishes = [], [], [], []
    total_bytes = 0
    for m, g, c, sp in zip(leaves_m, leaves_g, leaves_c, leaves_s):
        init, stages, kinds, fin, nbytes = build_leaf(m, g, c, sp)
        inits.append(init)
        stage_lists.append(stages)
        kind_lists.append(kinds)
        finishes.append(fin)
        total_bytes += int(nbytes)
    outs = pipeline.run_schedule(
        inits, stage_lists, kind_lists, cfg.overlap_depth()
    )
    ups, mems = [], []
    for st, fin in zip(outs, finishes):
        upd, new_m = fin(st)
        ups.append(upd)
        mems.append(new_m)
    return treedef.unflatten(ups), treedef.unflatten(mems), total_bytes


def bucketed_sync_gradients(
    cfg: SyncConfig,
    plan,
    memory_bufs,
    grad_tree,
    eta: Array,
    return_bufs: bool = False,
    pod_ks=None,
    grad_bufs=None,
    quant_key=None,
):
    """PARALLEL-MEM-SGD gradient exchange over flat buckets.

    Same contract as ``sparse_sync_gradients`` but the pytree is packed
    into the plan's few big (rows, cols) buffers first (see
    ``repro.core.buckets``): per-worker memory lives in bucket space
    (``memory_bufs``: one f32 buffer per bucket) and the all-gather runs
    once per bucket instead of once per leaf — over the packed uint32
    wire buffers when ``cfg.wire == "packed"`` (bit-identical results,
    ~2x fewer bytes; this path has no model-axis sharding to disturb). Rows never cross leaves'
    dtype groups; note that packing reshapes away any model-axis sharding,
    so this path targets data-parallel (or small-model-axis) meshes — the
    per-leaf path remains the choice for heavily tensor-parallel params.

    Returns (update_tree [f32 leaves, SUBTRACT from params],
    new_memory_bufs, bytes_per_worker_per_step) — plus the update's
    bucket-space (rows, cols) buffers when ``return_bufs`` (consumed by
    the delta stream, which re-encodes them without re-packing the tree).

    With ``cfg.pod_dynamic`` the hierarchical pod stage runs at RUNTIME
    k: ``pod_ks`` (one int32 scalar per bucket, e.g. a traced (n_buckets,)
    array indexed here) carries each bucket's live pod k, clipped to
    [1, ``pod_k_max_for_bucket``]; every buffer/wire/all-gather keeps
    the static k_max shape, so the same jitted step serves any k
    schedule with zero recompiles.

    ``grad_bufs`` (one f32 (rows, cols) buffer per bucket) substitutes
    for ``grad_tree``'s packing — the Qsparse-local-SGD driver passes
    its H-step bucket-space accumulator here (with ``eta=1.0``: the
    per-step stepsizes were already folded in by
    ``buckets.accumulate_local``). ``quant_key`` (a traced PRNG key,
    step already folded in) is required when ``cfg.wire_cfg.quant`` is
    set; each bucket folds its index, each quantize stage its level tag
    and axis indices.
    """
    from repro.core import buckets as bk

    cfg.validate(plan)
    if cfg.pod_dynamic and pod_ks is None:
        raise ValueError(
            "PodConfig.dynamic needs pod_ks (one live pod k "
            "per bucket) — pass the traced schedule the train step "
            "threads through, or unset pod.dynamic for static pod "
            "ratios"
        )
    if cfg.quant is not None and quant_key is None:
        raise ValueError(
            "WireConfig.quant needs quant_key (a threaded PRNG key; fold "
            "the step count in before calling) — stochastic rounding "
            "must draw fresh noise every sync"
        )
    value_dtype = jnp.dtype(cfg.value_dtype)
    all_axes = tuple(cfg.data_axes) + (
        (cfg.pod_axis,) if cfg.pod_axis else ()
    )
    if grad_bufs is not None:
        g_bufs = [b.astype(jnp.float32) for b in grad_bufs]
    else:
        g_bufs = bk.pack(plan, grad_tree, dtype=jnp.float32)
    # Build every bucket's stage chain up front, then emit in the
    # planned (possibly double-buffered) order. The finish closures run
    # after the schedule: they only combine already-computed values
    # (u, own, residual), so they impose no ordering of their own.
    inits, stage_lists, kind_lists, finishes = [], [], [], []
    total_bytes = 0
    for b, (spec, m, g) in enumerate(zip(plan.buckets, memory_bufs, g_bufs)):
        u = m + eta * g
        bkey = (jax.random.fold_in(quant_key, b)
                if quant_key is not None else None)
        if cfg.strategy == "dense" or spec.kind == "dense":
            stages, kinds, nbytes = _dense_stages(u.shape, u.dtype, all_axes)

            def finish(st, u=u):
                upd, own = st
                return upd, u - own
        elif cfg.strategy == "hierarchical" and cfg.pod_axis is not None:
            # true two-level: worker->pod at k_row, pod mean re-selected
            # at this bucket's own pod k (autotuned via cfg.pod_ratios),
            # pod residual folded into the bucket-space memory
            k_row = cfg.k_for(spec.cols)
            topk, densify = _pick_selection(cfg, k_row)
            if cfg.pod_dynamic:
                # runtime k: shapes at the static k_max, live k masks
                # the tail (clipped so a bad schedule can never overflow
                # the padded wire layout)
                n_data = _axis_size(tuple(cfg.data_axes))
                k_pod = cfg.pod_k_max_for_bucket(b, spec.cols, n_data)
                k_live = jnp.clip(
                    jnp.asarray(pod_ks[b], jnp.int32), 1, k_pod
                )
            else:
                k_pod = cfg.pod_k_for_bucket(b, spec.cols)
                k_live = None
            stages, kinds, level_bytes = _hier_stages(
                u.shape, u.dtype, k_row, k_pod,
                tuple(cfg.data_axes), cfg.pod_axis, value_dtype,
                topk=topk, densify=densify, wire=cfg.wire,
                k_pod_live=k_live, repack_boundary=cfg.repack,
                quant=cfg.quant, qkey=bkey,
            )
            nbytes = sum(level_bytes)

            def finish(st, u=u):
                upd, own, residual = st
                return upd, (u - own) + residual
        elif cfg.strategy in ("sparse_allgather", "hierarchical"):
            k_row = cfg.k_for(spec.cols)
            topk, densify = _pick_selection(cfg, k_row)
            stages, kinds, nbytes = _sparse_stages(
                u.shape, u.dtype, k_row, all_axes, value_dtype,
                topk=topk, densify=densify, wire=cfg.wire,
                quant=cfg.quant, qkey=bkey,
            )

            def finish(st, u=u):
                upd, own = st
                return upd, u - own
        else:
            raise ValueError(f"unknown sync strategy {cfg.strategy!r}")
        inits.append(u)
        stage_lists.append(stages)
        kind_lists.append(kinds)
        finishes.append(finish)
        total_bytes += int(nbytes)
    outs = pipeline.run_schedule(
        inits, stage_lists, kind_lists, cfg.overlap_depth()
    )
    ups, mems = [], []
    for st, fin in zip(outs, finishes):
        upd, new_m = fin(st)
        ups.append(upd)
        mems.append(new_m)
    if return_bufs:
        return bk.unpack(plan, ups), tuple(mems), total_bytes, ups
    return bk.unpack(plan, ups), tuple(mems), total_bytes


def repack_transport(wspec, buf, link=None):
    """The host/pod-boundary half of the header-aware repack transport:
    compact a k-padded wire buffer to its live payload
    (``encoding.repack``, sized by the buffer's own header word), ship
    exactly THAT many bytes across the slow link, and re-expand to the
    padded layout the in-jit consumer expects (``encoding.repad`` —
    bitwise equal to the buffer that went in, so the transport is
    invisible to everything downstream).

    Returns ``(padded_buf_or_future, wire_nbytes)``. With ``link=None``
    the round trip runs inline (the accounting/selfcheck path); with a
    ``pipeline.EmulatedLink``-style object the small buffer rides
    ``link.transfer(small_buf, wire_nbytes)`` and the returned future
    repads on ``.result()`` — drop it into a ``run_host_pipeline`` comm
    stage and the planner overlaps the (live-k-sized) transfer exactly
    like any gather."""
    from repro.core import encoding as enc

    small_spec, small_buf = enc.repack(wspec, buf)
    nbytes = small_spec.nbytes
    if link is None:
        return enc.repad(wspec, small_spec, small_buf), nbytes
    fut = link.transfer(small_buf, nbytes)

    class _Repad:
        def result(self):
            return enc.repad(wspec, small_spec, fut.result())

    return _Repad(), nbytes


def _sparse_leaf_bytes(cfg: SyncConfig, rows: int, cols: int,
                       pod_k: Optional[int] = None) -> int:
    """Exact per-worker bytes one sparse leaf/bucket puts on the wire:
    the packed ``WireSpec`` buffer size (header + bit-packed sections) or
    the raw (value_dtype, int32) pair arrays, per gather stage."""
    from repro.core import encoding as enc

    ks = [cfg.k_for(cols)]
    if cfg.strategy == "hierarchical" and cfg.pod_axis is not None:
        ks.append(pod_k if pod_k is not None else cfg.pod_k_for(cols))
    name = jnp.dtype(cfg.value_dtype).name
    return sum(
        enc.message_nbytes(rows, cols, k, name, cfg.wire, quant=cfg.quant)
        for k in ks
    )


def autotune_pod_ratios(cfg: SyncConfig, plan, u_bufs, n_data: int,
                        mass_target: Optional[float] = None,
                        k_caps: Optional[Sequence[int]] = None) -> tuple:
    """Per-bucket pod re-compression ratios from realized mass capture.

    The pod-stage selection sees the intra-pod mean, whose per-row
    support is bounded by ``n_data * k_row`` — shipping more slots than
    that is pure waste, and shipping the same k for every bucket wastes
    slots on buckets whose mass concentrates early. For each sparse
    bucket this picks the smallest k whose top-k captures
    ``cfg.pod_mass_target`` of the mass the pod stage can see at all,
    clamped to [k_min, support bound], and returns ratio = k / cols.
    Normalizing within the visible support (not the full row) is what
    makes the target meaningful per bucket: a heavy-tailed bucket
    reaches it in a handful of slots, a flat one keeps most of the
    bound.

    ``u_bufs`` leaves are concrete bucket buffers of u = m + eta*g:

    * ``(n_shards, rows, cols)`` — per-data-shard buffers. The pod
      stage is SIMULATED exactly: per-shard top-``k_row`` selection,
      densify, mean — the mass-capture curve is measured on the
      realized pod-mean proxy, so overlapping worker selections (highly
      correlated shard gradients) concentrate mass and shrink k.
    * ``(rows, cols)`` — a single global buffer; its top-``support``
      tail curve is the (more conservative) proxy.

    Host-side calibration: call once on concrete buffers and bake the
    result into ``SyncConfig.pod_ratios`` before building the jitted
    step (static wire layouts) — or, with ``cfg.pod_dynamic``, call it
    again MID-RUN on the live memory+gradient buffers and feed the new
    ks straight into the running step (the k-padded wire needs no
    re-jit). ``k_caps`` clamps each bucket's k to the static padded
    ceiling (``pod_k_max_for_bucket``) so a refresh can never outgrow
    the compiled buffers. Dense buckets get ratio 1.0 (never
    consulted).

    This is the mass-target mode of ``core.budget.BudgetController``
    (one measurement + allocator serves both this target sizing and the
    global ``SyncConfig.byte_budget`` water-filling); it delegates
    there so the two entry points can never drift apart."""
    from repro.core.budget import BudgetController

    ctl = BudgetController(cfg, plan, n_data, k_caps=k_caps)
    ks = ctl.allocate_mass_target(ctl.measure(u_bufs), mass_target)
    return ctl.ratios_of(ks)


def bucketed_message_bytes(cfg: SyncConfig, plan, *, by_level: bool = False,
                           n_data: Optional[int] = None,
                           pod_ks: Optional[Sequence[int]] = None):
    """Per-worker per-step transmitted bytes for a BucketPlan — the exact
    size of the buffers the sync all-gathers (index cost is the bucket's
    row-local ceil(log2 cols) bits when ``cfg.wire == "packed"``).

    With ``by_level=True`` returns ``{"intra", "cross", "total"}`` —
    the per-worker bytes that stay inside a pod vs cross the pod
    boundary on a ``(pod, data)`` mesh:

    * hierarchical: level 1 (k_row pairs, data-axis gather) is intra;
      only the re-compressed level-2 summary (this bucket's pod k)
      crosses pods.
    * flat strategies: the data-axis gather is intra, but the pod-axis
      gather then ships the CONCATENATED data-axis buffer — every
      worker lane re-transmits ``n_data`` messages across the boundary
      (pass ``n_data``; this is the fan-in the two-level scheme wins
      back).
    * dense buckets/strategy: the all-reduce moves ~buffer-size bytes
      at each level.

    ``total`` keeps the historical meaning (sum of the per-stage
    messages this worker emits) and equals the no-argument return.

    Runtime-k accounting: with ``cfg.pod_dynamic`` the level-2 message
    is k-PADDED — pass ``n_data`` and the default counts the padded
    gather buffer (``pod_k_max_for_bucket``), which is what the jitted
    step realizes. Pass ``pod_ks`` (the live per-bucket ks) to count
    the EFFECTIVE bytes instead: what a header-aware transport that
    re-packs to the live count (``encoding.LIVE_N_WORD``) would ship.
    """
    from repro.core import encoding as enc

    cfg.validate(plan)
    if by_level and cfg.pod_axis is not None and n_data is None and (
        cfg.strategy not in ("hierarchical", "dense")
    ):
        raise ValueError(
            "by_level accounting for a flat strategy on a pod mesh needs "
            "n_data (the concatenated data-axis buffer is what crosses "
            "the pod boundary)"
        )
    if (cfg.pod_dynamic and cfg.strategy == "hierarchical"
            and cfg.pod_axis is not None
            and pod_ks is None and n_data is None):
        raise ValueError(
            "pod-dynamic accounting needs n_data (the padded gather is "
            "shaped at the n_data-dependent k_max) or pod_ks (the live "
            "per-bucket ks, for effective-transport bytes)"
        )
    name = jnp.dtype(cfg.value_dtype).name
    intra = cross = total = 0
    pod = cfg.pod_axis is not None
    for b, spec in enumerate(plan.buckets):
        if cfg.strategy == "dense" or spec.kind == "dense":
            nb = spec.rows * spec.cols * 4
            total += nb
            intra += nb
            cross += nb if pod else 0
        elif cfg.strategy == "hierarchical" and pod:
            if pod_ks is not None:
                k2 = max(1, min(int(pod_ks[b]), spec.cols))
            elif cfg.pod_dynamic:
                k2 = cfg.pod_k_max_for_bucket(b, spec.cols, n_data)
            else:
                k2 = cfg.pod_k_for_bucket(b, spec.cols)
            lvl1 = enc.message_nbytes(
                spec.rows, spec.cols, cfg.k_for(spec.cols), name, cfg.wire,
                quant=cfg.quant)
            lvl2 = enc.message_nbytes(
                spec.rows, spec.cols, k2, name, cfg.wire, quant=cfg.quant)
            total += lvl1 + lvl2
            intra += lvl1
            cross += lvl2
        else:
            msg = _sparse_leaf_bytes(cfg, spec.rows, spec.cols)
            total += msg
            intra += msg
            if pod and n_data is not None:
                cross += n_data * msg
    if by_level:
        return {"intra": intra, "cross": cross, "total": total}
    return total


def amortized_bytes_per_step(cfg: SyncConfig, plan, *, by_level: bool = False,
                             n_data: Optional[int] = None,
                             pod_ks: Optional[Sequence[int]] = None):
    """Cross-worker bytes per OPTIMIZER step under Qsparse-local-SGD:
    with ``cfg.local_steps = H`` the workers communicate once every H
    steps, so the per-step cost is ``bucketed_message_bytes / H`` — the
    ~1/H scaling the local bench asserts. Same ``by_level`` contract,
    values as floats."""
    b = bucketed_message_bytes(cfg, plan, by_level=by_level, n_data=n_data,
                               pod_ks=pod_ks)
    H = max(1, cfg.local_steps)
    if isinstance(b, dict):
        return {k: v / H for k, v in b.items()}
    return b / H


def message_bytes(cfg: SyncConfig, params, col_axes=None) -> int:
    """Per-worker per-step transmitted bytes for a parameter pytree — the
    exact size of the gathered arrays (or packed wire buffers)."""
    cfg.validate()
    total = 0
    leaves, treedef = jax.tree.flatten(params)
    if col_axes is None:
        caxes = [None] * len(leaves)
    else:
        caxes = treedef.flatten_up_to(col_axes)
    for p, c in zip(leaves, caxes):
        d = p.size
        if cfg.strategy == "dense" or d < cfg.dense_below:
            total += d * 4
            continue
        ca = (c if c is not None else p.ndim - 1) % max(p.ndim, 1)
        C = p.shape[ca] if p.ndim else 1
        total += _sparse_leaf_bytes(cfg, d // max(C, 1), C)
    return total
