"""Operations and bytes a year scan needs, counted from the algorithm.

Per scenario and bin the algorithm reads the bin's load (and, under a
fault schedule, the bin's capacity multiplier and fault flag), takes one
policy step, folds the step into the Table II statistics and adds the
bin's load to one latency-histogram bucket. What an implementation adds
(compensation terms of the sums, branches of a switch it evaluates and
throws away, padding, re-reads) is not work here. Per scenario it reads
its parameters once and writes its summary row once.

Operations per bin, counted from the equations (an add, multiply,
divide, compare, select, min, max, ceil or clip bound each count one):

* policy steps: ``STEP_OPS`` — e.g. fifo: capacity per bin (2), arrivals
  plus queue (1), processed = min (1), new queue (1), mean queue (2),
  latency = base + mean queue / max(capacity, eps) (3), cost (1);
* the fault layer: gate, held-back arrivals, capacity scaling, the wait
  priced at nominal capacity and the fault-bin counters (12);
* the Table II folds: latency x load and load x SLO-ok (2), the SLO
  compare (1), six running sums (6), the SLO-ok bin count (1), the
  per-bin maximum (1) — 11;
* the histogram: the bucket key from exponent and mantissa (4) and one
  increment (1) — 5.

Bytes per bin: 4 for the float32 load; 4 + 4 more for the capacity and
flag under faults. Bytes per scenario: 6 float32 parameters in, the 174
float32 statistics and 2 of end state out.
"""
from __future__ import annotations

from typing import Dict, Tuple

STEP_OPS = {"fifo": 11, "quickscale": 8, "autoscale": 30, "shed": 15,
            "batch_window": 22}
FAULT_OPS = 12
FOLD_OPS = 11
HIST_OPS = 5
LOAD_BYTES = 4
FAULT_BYTES = 8
ROW_BYTES = 4 * (6 + 174 + 2)


def scan_work(rows_by_policy: Dict[str, int], t_bins: int,
              faulted: bool) -> Tuple[float, float]:
    """(operations, bytes) of a year scan of the given rows."""
    ops = nbytes = 0.0
    per_bin_bytes = LOAD_BYTES + (FAULT_BYTES if faulted else 0)
    for policy, n in rows_by_policy.items():
        per_bin = (STEP_OPS[policy] + FOLD_OPS + HIST_OPS
                   + (FAULT_OPS if faulted else 0))
        ops += float(n) * t_bins * per_bin
        nbytes += float(n) * (t_bins * per_bin_bytes + ROW_BYTES)
    return ops, nbytes
