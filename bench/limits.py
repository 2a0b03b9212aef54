"""Readings of the correctness control, which the limits are set from.

    python3 bench/limits.py --workloads <cell>,<cell> --seeds 1,2,3

The control is the plain reference computed one precision step below the
configuration's (``harness.check.CONTROL``: three-pass ``high`` matmuls
where the configuration states ``highest``), put in the program's place.
For each cell and seed, in one process: the weights, images and a sample
of requests as a run of the cell draws them from the seed (the cell's
``check_sample``, its keep-rate split), then the control's logits against
the reference's at ``highest``, read as ``bench/run.py`` reads the
program's. Prints one JSON line per cell and seed, and last the smallest
control reading of each number per cell. The program's own readings are
the ``check`` lines that every run of ``bench/run.py`` prints. Needs the
chip.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def readings(cell, seed: int, require_tpu: bool = True) -> dict:
    import jax

    from harness import check as C
    from harness import main as M
    from harness import traffic as T
    from harness import weights as W
    from harness.drive import Request

    M.device_info(cell.chips, require_tpu)
    M.enable_cache()
    config, mix = cell.config, cell.mix
    model, pruning = config["model"], config["pruning"]
    params = W.make_params(model, seed)
    jax.block_until_ready(params)
    scores = W.make_scores(model, pruning, config["structure_seed"])
    pool = T.image_pool(mix, mix["patches"], model["patch_size"] ** 2 * 3,
                        seed)
    n = int(mix["check_sample"])
    rates = T.keep_rates(mix, n, seed)
    images = T.seed_rng(seed, 4).integers(len(pool), size=n)
    reqs = [Request(uid=i, image=int(images[i]), r_t=float(rates[i]))
            for i in range(n)]
    args = (cell.reference, params, model, pruning, scores, pool, reqs)
    ref = C.reference_logits(*args)
    dtype, prec = C.CONTROL[config["numerics"]["matmul_precision"]]
    low = C.reference_logits(*args, dtype=dtype, precision=prec)
    groups = {r.uid: (r.r_t,) for r in reqs}
    return {"workload": cell.name, "seed": seed, "compared": n,
            "control": C.numbers(C.errors(low, ref), groups,
                                 config["check"]["tol"]),
            "control_candidates": C.candidates(low, ref)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    from harness import main as M
    from harness import spec
    for name in args.workloads.split(","):
        cell = spec.cell(name)
        rows = []
        for seed in [int(s) for s in args.seeds.split(",")]:
            try:
                row = readings(cell, seed)
            except M.NoAccelerator as e:
                print(f"limits.py: {e}; nothing was run", file=sys.stderr)
                return 3
            print(json.dumps(row), flush=True)
            rows.append(row)
        print(json.dumps({"workload": name, "seeds": len(rows),
                          "control_min": {
                              k: min(r["control"][k] for r in rows)
                              for k in rows[0]["control"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
