"""Execution configuration for the functional spMTTKRP engine.

``ExecutionConfig`` is a *frozen* (hashable) dataclass: it rides in the
static aux_data of :class:`repro.engine.state.EngineState`, so two states
with different configs hash to different jit cache entries and nothing
about execution policy is smuggled through mutable attributes.
"""
from __future__ import annotations

import dataclasses
import math

import jax

# Kappa policies understood by ``engine.init`` when it has to *build* the
# FLYCOO plans itself (raw COO input). "vmem" sizes partitions so a row
# tile fits VMEM (the DESIGN.md default); "fixed" uses ``kappa`` verbatim.
KAPPA_POLICIES = ("vmem", "fixed")

# Block schedules (see ``repro.core.partition``): "compact" emits only real
# blocks + a block->partition descriptor; "rect" pads every partition to
# the max partition's block count (the comparison baseline).
SCHEDULES = ("compact", "rect")

# Residency tiers: "full" keeps the whole FLYCOO layout device-resident
# (the classic engine); "stream" keeps only a double-buffered ring of
# partition-aligned chunks resident (the out-of-core tier,
# ``repro.engine.stream``); "auto" lets ``factory.make_engine`` pick —
# stream exactly when the resident layout would exceed
# ``device_budget_bytes``.
RESIDENCIES = ("auto", "full", "stream")

# Degradation-ladder backend ordering (consumed by ``repro.resilience``):
# on a compile/lowering failure each backend falls back to the next entry
# — strictly more portable, bitwise-identical output (the parity property
# every backend already CI-gates). ``residency`` has its own rung
# (full -> stream, in ``factory.make_engine``) and the streaming tier
# halves its chunk budget on OOM; see ``repro.resilience.ladder``.
BACKEND_LADDER = ("pallas_fused", "pallas", "xla", "ref")

# One budget, two tiers: when only the device (HBM) budget is given, the
# VMEM share the "vmem" kappa policy sizes row tiles against is derived
# from it — a fixed fraction capped at a typical per-core VMEM — so
# residency, rows_pp, and chunking can never contradict each other.
DEFAULT_VMEM_BYTES = 16 * 1024 * 1024
VMEM_FRACTION_OF_DEVICE = 8


def derive_vmem_budget(device_budget_bytes: int) -> int:
    """VMEM share of a device (HBM) budget: ``device/8`` capped at 16 MiB.
    The single derivation rule ``PlanSpec.canonical()`` and
    ``ExecutionConfig.resolve_rows_pp`` both use, so the row-tile sizing
    and the chunk sizing always answer to the same budget."""
    return max(1, min(DEFAULT_VMEM_BYTES,
                      device_budget_bytes // VMEM_FRACTION_OF_DEVICE))


def platform_default_interpret() -> bool:
    """Single source of the Pallas interpret-mode platform default: run the
    kernels through Mosaic only on a real TPU, interpret everywhere else.
    Both ``ExecutionConfig.resolve_interpret`` and ``repro.kernels.ops``
    defer here, so engine and kernels can never disagree."""
    return jax.default_backend() != "tpu"


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """Static execution policy for the engine (hashable, jit-cache safe).

    Attributes:
      backend: name in the backend registry (``xla`` | ``pallas`` | ``ref``).
      interpret: Pallas interpret mode. ``None`` = auto (interpret everywhere
        except on a real TPU), mirroring ``kernels.ops``.
      block_p: nonzeros per kernel block when the engine builds plans itself
        (paper's P; one sublane tile by default).
      kappa_policy: how ``engine.init`` picks the partition count for raw
        COO input — ``"vmem"`` (derive from rows_pp) or ``"fixed"``.
      kappa: partition count used when ``kappa_policy == "fixed"``.
      rows_pp: rows per partition for the ``"vmem"`` policy (``None`` =
        library default).
      precision: accumulation dtype name for the Hadamard partials
        (``"float32"`` unless a later mixed-precision PR widens this).
      donate: donate the layout buffers into the jitted scan (the paper's
        T_in/T_out swap without a second live copy). ``None`` = auto:
        donate only where XLA supports it (TPU/GPU).
      fuse_remap: let a fusing backend (one exposing ``fused_remap``, e.g.
        ``pallas_fused``) emit the Alg. 3 remap scatter inside its kernel
        pass instead of the full-``S_max`` XLA scatter in the scan step.
        ``False`` forces the XLA scatter path for any backend (the
        comparison baseline). Moot on a pinned state, which never remaps
        (``engine.state``).
      dedup: build the in-block factor-row dedup tables for backends that
        consume them (``needs_dedup``). ``False`` installs the trivial
        tables (one row DMA per slot) — same kernels, no host-side
        per-block sort; a plan-space point that trades preprocessing time
        against kernel DMA traffic.
      vmem_budget_bytes: VMEM budget the ``"vmem"`` kappa policy sizes row
        tiles against when ``rows_pp`` is not given explicitly. ``None`` =
        library default tile (``partition.DEFAULT_ROWS_PER_PARTITION``).
      rank_hint: rank R used to convert the VMEM budget into rows (the
        paper's default R=32); only consulted when ``vmem_budget_bytes``
        is set.
      schedule: block schedule used when ``engine.init`` builds plans from
        raw COO input — ``"compact"`` (load-balanced grid of real blocks,
        the default) or ``"rect"`` (rectangular comparison baseline). A
        prebuilt ``FlycooTensor``'s plans carry their own schedule and
        take precedence.
      residency: memory tier — ``"full"`` (whole layout device-resident),
        ``"stream"`` (out-of-core chunk ring, ``repro.engine.stream``), or
        ``"auto"`` (factory picks by comparing the resident footprint to
        ``device_budget_bytes``).
      chunk_nnz: target nonzeros per streamed chunk (partition-aligned;
        the planner rounds to whole partitions). ``None`` = derive from
        ``device_budget_bytes`` / the library default.
      device_budget_bytes: device (HBM) budget the streaming tier sizes
        its resident chunk ring against, and the threshold ``"auto"``
        residency compares the full layout to. Also the root of the
        derived VMEM budget (``derive_vmem_budget``) when
        ``vmem_budget_bytes`` is not set.
      stream_ring: number of resident chunk buffers in the streaming ring
        (2 = classic double buffering: chunk k computes while k+1 uploads).
    """

    backend: str = "xla"
    interpret: bool | None = None
    block_p: int = 128
    kappa_policy: str = "vmem"
    kappa: int | None = None
    rows_pp: int | None = None
    precision: str = "float32"
    donate: bool | None = None
    fuse_remap: bool = True
    dedup: bool = True
    vmem_budget_bytes: int | None = None
    rank_hint: int = 32
    schedule: str = "compact"
    residency: str = "auto"
    chunk_nnz: int | None = None
    device_budget_bytes: int | None = None
    stream_ring: int = 2

    def __post_init__(self):
        if self.kappa_policy not in KAPPA_POLICIES:
            raise ValueError(
                f"kappa_policy {self.kappa_policy!r} not in {KAPPA_POLICIES}")
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"schedule {self.schedule!r} not in {SCHEDULES}")
        if self.residency not in RESIDENCIES:
            raise ValueError(
                f"residency {self.residency!r} not in {RESIDENCIES}")
        if self.kappa_policy == "fixed" and self.kappa is None:
            raise ValueError("kappa_policy='fixed' requires kappa")
        if self.vmem_budget_bytes is not None and self.vmem_budget_bytes < 1:
            raise ValueError("vmem_budget_bytes must be positive")
        if self.chunk_nnz is not None and self.chunk_nnz < 1:
            raise ValueError("chunk_nnz must be positive")
        if (self.device_budget_bytes is not None
                and self.device_budget_bytes < 1):
            raise ValueError("device_budget_bytes must be positive")
        if self.stream_ring < 1:
            raise ValueError("stream_ring must be >= 1")
        if (self.vmem_budget_bytes is not None
                and self.device_budget_bytes is not None
                and self.vmem_budget_bytes > self.device_budget_bytes):
            raise ValueError(
                "contradictory budgets: vmem_budget_bytes "
                f"({self.vmem_budget_bytes}) exceeds device_budget_bytes "
                f"({self.device_budget_bytes})")

    # ------------------------------------------------------------ resolution
    def resolve_interpret(self) -> bool:
        if self.interpret is None:
            return platform_default_interpret()
        return bool(self.interpret)

    def resolve_donate(self) -> bool:
        if self.donate is None:
            # CPU XLA ignores donation and warns; keep auto mode quiet there.
            return jax.default_backend() in ("tpu", "gpu")
        return bool(self.donate)

    def accum_dtype(self):
        import jax.numpy as jnp

        return jnp.dtype(self.precision)

    def resolve_rows_pp(self) -> int | None:
        """Rows per partition for the ``"vmem"`` kappa policy.

        Explicit ``rows_pp`` wins. Otherwise, with a ``vmem_budget_bytes``
        the tile is sized so the fused kernel's resident f32 output tile
        (``rows_pp * rank_hint * 4`` bytes) uses at most half the budget —
        the other half is reserved for the double-buffered factor-row
        staging and the one-hot operand. ``None`` means the library default
        tile (``partition.DEFAULT_ROWS_PER_PARTITION``).
        """
        if self.rows_pp is not None:
            return self.rows_pp
        vmem = self.resolve_vmem_budget()
        if vmem is None:
            return None
        return max(8, vmem // (2 * 4 * self.rank_hint))

    def resolve_vmem_budget(self) -> int | None:
        """The one VMEM budget everything answers to: explicit
        ``vmem_budget_bytes`` wins; otherwise it is derived from
        ``device_budget_bytes`` (``derive_vmem_budget``); ``None`` when
        neither budget is set."""
        if self.vmem_budget_bytes is not None:
            return self.vmem_budget_bytes
        if self.device_budget_bytes is not None:
            return derive_vmem_budget(self.device_budget_bytes)
        return None

    def kappa_for(self, dim: int, n_dev: int = 1) -> int:
        """Partition count for a mode of size ``dim`` under this config's
        kappa policy, rounded so each of ``n_dev`` devices owns an equal,
        contiguous run of partitions (``kappa % n_dev == 0``, and
        ``kappa <= dim`` unless the mode has fewer rows than devices: then
        every device owns one partition and the surplus ones are empty).

        This is the single source of the per-device rounding rule — the
        engine, ``core.distributed.build_sharded_flycoo``, and benchmarks
        all derive their sharded partition counts from it.
        """
        if self.kappa_policy == "fixed":
            base = self.kappa
        else:
            from repro.core.partition import choose_kappa

            rows_pp = self.resolve_rows_pp()
            base = choose_kappa(dim, rows_pp) if rows_pp else choose_kappa(dim)
        if n_dev <= 1:
            return min(base, dim)
        kappa = max(n_dev, math.ceil(base / n_dev) * n_dev)
        return min(kappa, max(n_dev, (dim // n_dev) * n_dev))


__all__ = ["ExecutionConfig", "KAPPA_POLICIES", "SCHEDULES", "RESIDENCIES",
           "BACKEND_LADDER", "derive_vmem_budget",
           "platform_default_interpret"]
