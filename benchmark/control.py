"""The control of the scheduling answer: the reference, put in the
program's place with a fault, has to come out NOT correct.

    python benchmark/control.py --config baseline-50k-5k --seeds 1,2,3

The reference as a scheduler is ``reference.place_first_fit``: whole gangs,
first fit.  Without a fault its binds must pass ``Ledger.check_binds`` (so
that the fault is what fails); with the fault the configuration names
under ``control.placement`` they must not.  No device is involved: the
same numpy runs anywhere, at the cell's own size.  (The controls in a lower
precision, of the edge round's binds and of the what-if answers, need the
state a served run ends in: every run of run.py reads them and prints
them in its notes.)  Prints one JSON line per seed; exit 0 only if
every seed's control failed the comparison and every exact run passed it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reference  # noqa: E402


def populate(config: dict, seed: int) -> reference.Ledger:
    """The configuration's population, live in a fresh ledger."""
    ledger = reference.Ledger(config, seed)
    ledger.add(*ledger.make_population())
    return ledger


def read(config: dict, seed: int, fault: str) -> dict:
    ledger = populate(config, seed)
    numbers, _ = ledger.check_binds(reference.place_first_fit(ledger, fault))
    return numbers


def read_edge(config: dict, seed: int, rounds: int = 12):
    """The edge round's control without a served run: the population
    spread round-robin over the nodes (every node partly used, as the
    program leaves them), then the round's pods placed by the reference
    over the exact plane and over one summed in ``control.edge``."""
    import numpy as np
    ledger = populate(config, seed)
    n = len(ledger.node_names)
    rows = [(i % n, np.array(pod[:2] + (1,), np.int64))
            for i, pod in enumerate(ledger.pods.values())]
    used = reference.summed_plane(ledger.alloc, rows, "exact")
    requests = reference.edge_requests(
        ledger.alloc, used, int(min(config["request_mix"]["memory_bytes"])),
        rounds)
    precision = config["control"]["edge"]
    return (reference.edge_control(ledger.alloc, used, requests, "exact"),
            reference.edge_control(
                ledger.alloc,
                reference.summed_plane(ledger.alloc, rows, precision),
                requests, precision))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--fault", default=None,
                    help="default: the configuration's control.placement; "
                         "'edge' reads the edge round's control")
    args = ap.parse_args(argv)
    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        config = json.load(f)
    fault = args.fault or config["control"]["placement"]
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        if fault == "edge":
            exact, broken = read_edge(config, seed)
        else:
            exact, broken = read(config, seed, "exact"), read(config, seed,
                                                              fault)
        sound = not any(exact.values())
        caught = any(broken[k] > reference.LIMITS[k] for k in broken)
        ok = ok and sound and caught
        print(json.dumps({"config": args.config, "seed": seed, "fault": fault,
                          "exact": exact, "control": broken,
                          "exact_passes": sound, "control_fails": caught}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
