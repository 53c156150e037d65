"""Work, bytes and peaks: the arithmetic behind a kernel's roofline share.

Counted from shapes, the same whatever implements the kernel:

* work: for each evaluated row, the design's *raw* event graph, one add
  and one max per event and per cross edge.  Every event has at most one
  cross edge (a read's data edge from its write, a write's back-pressure
  edge from the read that frees its slot), so a row costs ``4 * E_raw``
  operations.  A condensed rung counts the raw graph too: the work it
  saved is counted as if it had done it.
* bytes: each row's depth row in (int32 per FIFO) and result row out
  (128 float32 lanes); each dispatch also reads the six event tables of
  the graph it runs on once (float32/int32 per padded event).  A
  cross-design dispatch instead reads each row's own tables.

The share of a kernel is the least time the chip could take for those
bytes and that work, over the kernel's device time.  Peaks are per
``device_kind``; a device missing from the table is an error.  No float32
vector peak is published for the TPU v5e, so the work has no bound and
every share here is of the HBM bound.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

LANES = 128
OUT_LANES = 128
#: event tables a kernel reads per graph: delta, seg_start, is_read,
#: has_data, data_idx, end_bonus
N_TABLES = 6
WORD = 4

#: published per-chip peaks, keyed by ``device.device_kind``
PEAKS: Dict[str, dict] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e)",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add its row to PEAKS") from None


def e_pad(n_events: int) -> int:
    """Events padded to whole 128-lane vectors (at least one)."""
    return max(LANES, -(-max(n_events, 1) // LANES) * LANES)


def row_work(n_raw_events: int) -> int:
    """Operations one row's fixpoint needs on the raw graph."""
    return 2 * (n_raw_events + n_raw_events)


def table_bytes(n_events: int) -> int:
    return N_TABLES * e_pad(n_events) * WORD


def row_bytes(n_fifos: int) -> int:
    return n_fifos * WORD + OUT_LANES * WORD


@dataclasses.dataclass
class KernelTally:
    """Dispatches, rows, bytes and work counted for one kernel."""

    dispatches: int = 0
    rows: int = 0
    bytes: int = 0
    work: int = 0

    def add(self, rows: int, n_raw_events: int, n_events: int,
            n_fifos: int, per_row_tables: bool = False,
            new_dispatch: bool = True) -> None:
        """One dispatch of ``rows`` real rows on a graph of ``n_events``
        (``n_raw_events`` before condensation) with ``n_fifos`` FIFOs;
        ``new_dispatch=False`` adds rows of another design to the last
        cross-design dispatch."""
        self.dispatches += int(new_dispatch)
        self.rows += rows
        self.work += rows * row_work(n_raw_events)
        per_row = row_bytes(n_fifos)
        if per_row_tables:
            self.bytes += rows * (per_row + table_bytes(n_events))
        else:
            self.bytes += rows * per_row + table_bytes(n_events)


def share(tally: KernelTally, kernel_s: float, device_kind: str
          ) -> Optional[float]:
    """Percent of the HBM roofline that ``kernel_s`` seconds of device
    time reached for the tallied bytes; None with no time to divide."""
    if not kernel_s or kernel_s <= 0 or tally.rows == 0:
        return None
    bound_s = tally.bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * bound_s / kernel_s
