"""tpudist.serve — batched inference engine with latency-SLO verdicts.

The fourth subsystem beside ``elastic/``, ``tune/`` and ``obs/``: the
"millions of users" half of the north star. A prefill/decode-split
engine over the training models (``models.transformer`` / ``models.moe``
grow a prefill and a paged incremental forward — serve does not fork
the model), with

* a paged KV pool sharded on the existing mesh machinery (pages mapped
  to slots by a host allocator, GQA-compact head layout,
  ``parallel.sharding.paged_kv_cache_specs``) —
  :mod:`tpudist.serve.kvcache`;
* exactly TWO compiled programs per run — one prefill, one ``lax.scan``
  decode superstep over the whole slot batch — :mod:`tpudist.serve.engine`;
* a continuous-batching scheduler: Poisson arrivals, admission into
  free slots, mid-scan completion — :mod:`tpudist.serve.scheduler`;
* latency-SLO verdicts (p50/p99 TTFT, inter-token latency, tokens/s/chip)
  through the shared :mod:`tpudist.rules` table —
  :mod:`tpudist.serve.slo`;
* a measured-probe autotuner for the decode superstep, the page size
  and the speculation window on the PR-4 fingerprint-cache machinery —
  :mod:`tpudist.serve.tune`;
* the resilience plane (PR 15): admission control with deadline-based
  load shedding and an exactly-checked arrival partition, a hysteretic
  pressure controller over a pre-compiled decode_k ladder, and honest
  lost-slot accounting under the launcher's requeue loop —
  :mod:`tpudist.serve.resilience`;
* the jax-free overload + serve fault drill and its invariant verifier
  (``python -m tpudist.serve.drill``) — :mod:`tpudist.serve.drill`.

Entry point: ``python -m tpudist.serve`` (:mod:`tpudist.serve.cli`).

This ``__init__`` stays jax-free (only :mod:`tpudist.serve.slo` is
imported eagerly): the offline report CLI imports the SLO math on
machines with no accelerator stack installed.
"""

from tpudist.serve.slo import (LatencyStats, grade, percentile,  # noqa: F401
                               serve_status)

__all__ = ["LatencyStats", "grade", "percentile", "serve_status"]
