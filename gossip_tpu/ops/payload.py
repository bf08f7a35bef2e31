"""The replicated-payload row: what one mergeable payload gives the
exchange drivers.

The CRDT (ops/crdt), replicated-log (ops/logs) and LWW-register
(ops/registers) payloads ride the SAME pull round — sample, drop, cut,
gather, merge — and differ only in the fields below.  Each ops module
defines its one ``ROW``; models/payload.row_for picks it from the
workload config, and the single-device frame (models/payload) and the
node-mesh frame (parallel/sharded_payload) read nothing else about a
payload.  A new payload lands as its kernels plus one row.

The row is a code table, not an option: no config field or flag
selects or edits it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional


def _no_check(cfg, sharded: bool) -> None:
    """Payloads whose every config has an exchange driver."""


@dataclass(frozen=True)
class PayloadRow:
    """One payload's entries; every callable takes the workload config
    first.  ``label`` names the payload everywhere a name is visible:
    the compile label (``<label>`` on the mesh, ``<label>_solo`` on one
    device), the round-metrics flag and driver name
    (``simulate_<until|curve>_<label>_sharded``) and the engine strings
    the CLI and backend report.  ``column`` is the round-metrics
    convergence column; ``engine`` the ops/nemesis capability row."""

    label: str
    column: str
    engine: str
    # refusals, raised before anything is built
    check_mode: Callable            # (proto) -> None
    check_reachable: Callable       # (cfg, run) -> None
    # state row: [n, width(cfg, n)] of dtype(cfg), all zero at round 0
    width: Callable                 # (cfg, n) -> int
    dtype: Callable                 # (cfg) -> dtype
    # the injection program as runtime operands on the table tail
    inject_args: Callable           # (cfg, n) -> tuple
    split_inject: Callable          # (cfg, tbl) -> (head, inj)
    inject_rows: Callable   # (cfg, inj, gids, round_, n, origin, fault)
    apply_inject: Callable          # (cfg, val, rows) -> val
    # the exchange
    merge: Callable                 # (cfg, a, b) -> joined
    pull_merge: Callable            # (cfg, rows_all, partners, n)
    # ground truth and observables
    ground_truth: Callable          # (cfg, inj, fault, n, origin) -> row
    payload_count: Callable         # (cfg, rows, alive) -> f32 mass
    truth_summary: Callable         # (cfg, truth, n) -> host summary
    check_cfg: Callable = _no_check  # (cfg, sharded) -> None
    # the liar program (None: the payload refuses byzantine faults)
    pull_merge_byz: Optional[Callable] = None
    # (cfg, rows_all, partners, n, byz, round_, gids, origin,
    #  fault, defend)
    honest_mask: Optional[Callable] = None
    # (cfg, inj, fault, n, origin, honest) -> component mask
    byz_conv_frac: Optional[Callable] = None
    # (cfg, rows, truth, honest_alive, mask) -> f32

    @property
    def byz(self) -> bool:
        """Does this payload run scripted liar programs?"""
        return self.pull_merge_byz is not None
