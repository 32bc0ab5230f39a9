"""Public jit'd wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode — the kernel
body runs under the Pallas interpreter, validating BlockSpec tiling and
numerics; on TPU the same calls compile to Mosaic.  ``interpret=None``
auto-detects.
"""
from __future__ import annotations

import jax

from repro.kernels import distance as _distance
from repro.kernels import fused_topk as _fused_topk
from repro.kernels import list_scan as _list_scan
from repro.kernels import pq_adc as _pq_adc
from repro.kernels import ref as ref  # re-export oracles


# Backend detection is resolved once (jax.default_backend() initializes
# the platform backend — too heavy for the per-op hot path) and cached;
# tests and TPU-vs-interpret comparisons override via
# set_default_interpret().
_DEFAULT_INTERPRET: bool | None = None


def default_interpret() -> bool:
    """The cached module-level interpret default (True off-TPU)."""
    global _DEFAULT_INTERPRET
    if _DEFAULT_INTERPRET is None:
        _DEFAULT_INTERPRET = jax.default_backend() != "tpu"
    return _DEFAULT_INTERPRET


def set_default_interpret(value: bool | None) -> None:
    """Override (or, with ``None``, re-arm auto-detection of) the
    interpret default used when a call site passes ``interpret=None``."""
    global _DEFAULT_INTERPRET
    _DEFAULT_INTERPRET = value


def _auto_interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    return default_interpret()


def l2_distance(q, x, *, interpret: bool | None = None, **kw):
    return _distance.l2_distance(
        q, x, interpret=_auto_interpret(interpret), **kw)


def adc_lookup(codes, table, *, interpret: bool | None = None, **kw):
    return _pq_adc.adc_lookup(
        codes, table, interpret=_auto_interpret(interpret), **kw)


def l2_topk(q, x, k=10, *, interpret: bool | None = None, **kw):
    return _fused_topk.l2_topk(
        q, x, k, interpret=_auto_interpret(interpret), **kw)


def list_scan(queries, list_vecs, probe, ids, *,
              interpret: bool | None = None):
    return _list_scan.list_scan(
        queries, list_vecs, probe, ids, interpret=_auto_interpret(interpret))
