"""Readings of a cell's control and faults, from which its limits are set.

    python bench/control.py --workload <name> --seeds <a,b,c> [--requests N]

Nothing of the program runs here: the plain reference is put in its
place, computed the way the control or a fault computes it, and
compared with the reference proper by the same numbers a run of the cell
compares.  One JSON line per seed on stdout.

- Scan cells: the control answers the cell's first ``--requests``
  requests in float32, the precision below the configuration's float64.
- The training cell: the control is the reference with every matmul
  operand in fp8 (below the configuration's bfloat16); the fault
  ``half_batch`` leaves out half of each step's sequences and takes the
  mean over the rest.  A step that returns its state unchanged reads 1
  on ``update_norm_gap`` by construction and needs no run.

The benchmark's own runs never run this.  At the cell's size it needs
the chip (the training reference); ``bench/tests`` runs it small.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def scan_readings(cfg: dict, traffic: dict, ref, seed: int,
                  n_requests: int) -> dict:
    import numpy as np

    from bench.drivers import scan
    table = scan.load_generator(cfg["generator"]).generate(cfg["data"], seed)
    n = len(next(iter(table.values())))
    filters = scan.thresholds(traffic, table)
    stream = scan.requests(traffic, n, seed)
    worst: dict[str, float] = {}
    for _ in range(n_requests):
        rows = next(stream)
        got = ref.answer(table, traffic, filters, rows,
                         float_dtype=np.float32)
        want = ref.answer(table, traffic, filters, rows)
        for k, v in ref.gaps(got, want).items():
            worst[k] = max(worst.get(k, 0), v)
    return {"control_float32": worst}


def train_readings(cfg: dict, ref, seed: int, checked: int) -> dict:
    from bench.gen import lm_corpus
    seq, batch = cfg["train"]["seq_len"], cfg["train"]["batch"]
    corpus = lm_corpus.generate(cfg["corpus"], seq,
                                cfg["model"]["vocab_size"], seed)
    n = len(corpus["tokens"])
    batches = [corpus["tokens"][lm_corpus.rows_for_step(seed, s, n, batch)]
               for s in range(checked)]
    want = ref.first_steps(cfg, seed, batches)
    fp8 = ref.first_steps(cfg, seed, batches, matmul_dtype="float8")
    half = ref.first_steps(cfg, seed, [b[:len(b) // 2] for b in batches])
    return {"reference_losses": want["losses"],
            "control_fp8": ref.gaps(fp8, want),
            "half_batch": ref.gaps(half, want)}


def main(argv: list[str] | None = None, *,
         config_override: dict | None = None) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import load_module, run
    spec = run.load_spec(ROOT)
    work, _, cfg, traffic = run.load_cell(args.workload, spec, ROOT)
    if config_override:
        cfg = run._merge(cfg, config_override)
    ref = load_module(ROOT / "bench" / "configs"
                           / f"{work['config']}_ref.py",
                           f"bench_ref_{work['config']}")
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        if traffic["kind"] == "train":
            got = train_readings(cfg, ref, seed, traffic["checked_steps"])
        else:
            got = scan_readings(cfg, traffic, ref, seed, args.requests)
        line = {"workload": args.workload, "seed": seed, **got}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


if __name__ == "__main__":
    main()
