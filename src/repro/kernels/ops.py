"""Jit'd public wrappers for the Pallas kernels.

On a TPU the kernels compile through Mosaic; on any other backend the
default is ``interpret=True`` (the kernel body runs as traced JAX ops), so
correctness is validated end-to-end on the CPU too. Dry-run/roofline lowering uses the
XLA reference paths so ``cost_analysis()`` reports honest HLO (DESIGN.md §6).

Interpret resolution is policy, not plumbing: every wrapper accepts either
an explicit ``interpret=`` or an :class:`repro.engine.ExecutionConfig`
(``config=``) and defers to ``config.resolve_interpret()`` — the same
policy object that keys the engine's backend registry. The platform
default itself lives in ONE place,
:func:`repro.engine.config.platform_default_interpret`, which both the
config and these wrappers consult, so kernel and engine can never disagree
about execution mode.
"""
from __future__ import annotations

from repro.engine.config import platform_default_interpret

from . import ref
from .mttkrp_kernel import mttkrp_fused as _mttkrp_fused
from .mttkrp_kernel import mttkrp_fused_compact as _mttkrp_fused_compact
from .mttkrp_kernel import mttkrp_fused_gather as _mttkrp_fused_gather
from .mttkrp_kernel import (
    mttkrp_fused_gather_compact as _mttkrp_fused_gather_compact)
from .mttkrp_kernel import mttkrp_fused_remap as _mttkrp_fused_remap
from .mttkrp_kernel import (
    mttkrp_fused_remap_compact as _mttkrp_fused_remap_compact)
from .lru_scan import lru_scan as _lru_scan
from .wkv6 import wkv6 as _wkv6


def resolve_interpret(interpret: bool | None = None, config=None) -> bool:
    """One resolution rule for all kernels: explicit flag > config policy >
    platform default (interpret everywhere but TPU)."""
    if interpret is not None:
        return bool(interpret)
    if config is not None:
        return config.resolve_interpret()
    return platform_default_interpret()


def mttkrp_fused(gathered, val, lrow, *, kappa, rows_pp, blocks_pp, block_p,
                 interpret: bool | None = None, config=None):
    return _mttkrp_fused(gathered, val, lrow, kappa=kappa, rows_pp=rows_pp,
                         blocks_pp=blocks_pp, block_p=block_p,
                         interpret=resolve_interpret(interpret, config))


def mttkrp_fused_compact(gathered, val, lrow, bpart, *, kappa, rows_pp,
                         nblocks, block_p, interpret: bool | None = None,
                         config=None):
    """Descriptor-driven compact-schedule EC baseline (1-D block grid)."""
    return _mttkrp_fused_compact(
        gathered, val, lrow, bpart, kappa=kappa, rows_pp=rows_pp,
        nblocks=nblocks, block_p=block_p,
        interpret=resolve_interpret(interpret, config))


def mttkrp_fused_gather(val, lrow, lidx, factors, *, kappa, rows_pp,
                        blocks_pp, block_p, interpret: bool | None = None,
                        config=None):
    """Zero-HBM-intermediate EC: factor rows gathered inside the kernel."""
    return _mttkrp_fused_gather(
        val, lrow, lidx, tuple(factors), kappa=kappa, rows_pp=rows_pp,
        blocks_pp=blocks_pp, block_p=block_p,
        interpret=resolve_interpret(interpret, config))


def mttkrp_fused_gather_compact(val, lrow, upos, bpart, uidx, nuniq,
                                factors, *, kappa, rows_pp, nblocks,
                                block_p, interpret: bool | None = None,
                                config=None):
    """Compact fused gather with in-block factor-row dedup (U <= P DMAs)."""
    return _mttkrp_fused_gather_compact(
        val, lrow, upos, bpart, uidx, nuniq, tuple(factors), kappa=kappa,
        rows_pp=rows_pp, nblocks=nblocks, block_p=block_p,
        interpret=resolve_interpret(interpret, config))


def mttkrp_fused_remap(val, idx, alpha, lrow, lidx, factors, *, kappa,
                       rows_pp, blocks_pp, block_p, smax, next_mode,
                       interpret: bool | None = None, config=None):
    """Fused EC + Alg. 3 remap scatter (one Pallas pass, four outputs)."""
    return _mttkrp_fused_remap(
        val, idx, alpha, lrow, lidx, tuple(factors), kappa=kappa,
        rows_pp=rows_pp, blocks_pp=blocks_pp, block_p=block_p, smax=smax,
        next_mode=next_mode,
        interpret=resolve_interpret(interpret, config))


def mttkrp_fused_remap_compact(val, idx, alpha, lrow, upos, bpart, uidx,
                               nuniq, factors, *, kappa, rows_pp, nblocks,
                               block_p, smax, next_mode,
                               interpret: bool | None = None, config=None):
    """Compact fused EC + remap with in-block dedup (one pass, 4 outputs)."""
    return _mttkrp_fused_remap_compact(
        val, idx, alpha, lrow, upos, bpart, uidx, nuniq, tuple(factors),
        kappa=kappa, rows_pp=rows_pp, nblocks=nblocks, block_p=block_p,
        smax=smax, next_mode=next_mode,
        interpret=resolve_interpret(interpret, config))


def lru_scan(a, x, *, chunk: int = 32, interpret: bool | None = None,
             config=None):
    return _lru_scan(a, x, chunk=chunk,
                     interpret=resolve_interpret(interpret, config))


def wkv6(r, k, w, v, u, *, chunk: int = 16, interpret: bool | None = None,
         config=None):
    return _wkv6(r, k, w, v, u, chunk=chunk,
                 interpret=resolve_interpret(interpret, config))


__all__ = ["mttkrp_fused", "mttkrp_fused_compact", "mttkrp_fused_gather",
           "mttkrp_fused_gather_compact", "mttkrp_fused_remap",
           "mttkrp_fused_remap_compact", "lru_scan", "wkv6", "ref",
           "resolve_interpret"]
