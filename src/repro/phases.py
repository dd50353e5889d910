"""The phases of a product, named once for the device scopes and the spans.

The engine's traced executables wrap each phase below in :func:`scope`, a
``jax.named_scope("opsparse.<phase>")``: the name lands in the HLO
``op_name`` metadata of every op traced inside it, so a profiler trace
can charge each device op to its phase.  Scopes change metadata only:
the compiled program is otherwise the same.  The engine's telemetry
spans (``repro.engine.telemetry``) become profiler annotations under the
same prefix.

A leaf module on purpose: ``core``, ``kernels`` and ``engine`` all use
it, and it imports JAX only when a scope is opened.
"""
from __future__ import annotations

PREFIX = "opsparse."

NPROD = "nprod"                  # n_prod per row into C.rpt (nprod_into_rpt)
BIN = "bin"                      # row binning (bin_rows)
FALLBACK = "fallback"            # the ESC fallback rung of the hash ladder
ROWPTR = "rowptr"                # total nnz and C.rpt (exclusive_sum_in_place)
EPILOGUE_FALLBACK = "epilogue.fallback"   # scatter_sub_rows
ESC_EXPAND = "esc.expand"        # expand_products
ESC_SORT = "esc.sort"            # the (row, col) sort of the products
ESC_COMPRESS = "esc.compress"    # duplicates merged into C


def hash_rung(rung: int) -> str:
    """Rung ``rung``'s hash kernel (fused, symbolic or numeric) and the
    scatter of its per-row nnz."""
    return f"hash.r{rung}"


def epilogue_rung(rung: int) -> str:
    """Rung ``rung``'s epilogue: the sort of its dumped tables and their
    entries' way into C, by gather or by scatter (``numeric_epilogue``)."""
    return f"epilogue.r{rung}"


def scope(phase: str):
    """The device scope of ``phase``: ``jax.named_scope("opsparse.<phase>")``."""
    import jax
    return jax.named_scope(PREFIX + phase)
