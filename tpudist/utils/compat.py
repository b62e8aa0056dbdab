"""Normaliser for ``Compiled.memory_analysis()``, whose return shape
differs by backend (object, per-device list, ``None``, or a raise)."""

from __future__ import annotations


def memory_analysis(compiled) -> dict:
    """``Compiled.memory_analysis()`` as one flat byte-count dict, or
    ``{}`` when the backend can't say.

    A backend returns an object with ``*_size_in_bytes`` attributes, a
    per-device list of them, ``None``, or raises (memory planning is an
    XLA:TPU/GPU feature). The ledger treats a missing analysis as zero
    known temp with the gap flagged, so this normalizer degrades to
    ``{}`` rather than raising.
    """
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if isinstance(mem, (list, tuple)):
        mem = mem[0] if mem else None
    if mem is None:
        return {}
    out = {}
    for key, attr in (("argument_bytes", "argument_size_in_bytes"),
                      ("output_bytes", "output_size_in_bytes"),
                      ("temp_bytes", "temp_size_in_bytes"),
                      ("generated_code_bytes",
                       "generated_code_size_in_bytes"),
                      ("alias_bytes", "alias_size_in_bytes")):
        val = getattr(mem, attr, None)
        if val is not None:
            try:
                out[key] = int(val)
            except (TypeError, ValueError):
                pass
    return out
