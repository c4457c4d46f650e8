#!/usr/bin/env python3
"""Rehearsal 4: would a cell's ``correct`` catch a lower precision?

The plain reference alone, at the cell's published widths, on whatever
JAX finds (the CPU here: minutes per seed).  It runs the reference
twice on the same seeded weights and inputs, once as it is and once
with every matmul operand rounded to a lower type, and holds the second
to the cell's own check against the first.  No program under test, no
chip, no result line: it calibrates the yardstick and measures nothing.

  JAX_PLATFORMS=cpu python benchmarks/rehearsals/precision_controls.py \
      --workload train-flagship-t1024 --seeds 1 2 3 [--dtype bfloat16]

Train cells: the mix's first records, B at a time, ``loss_batches`` of
them; caught when the root mean square of the loss differences is over
``loss_tolerance``.  Serve cells: ``check_per_class`` requests of each
class with a seeded continuation; at every position the rounded
reference's top token is scored by the exact one, as the runner scores
the engine's tokens; caught when any is further than ``logit_margin``
from the exact top logit.
"""

import argparse
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def _say(msg):
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


def _train(cell, reference, params, seed, vocab, dtype):
    import numpy as np

    from benchmarks import traffic

    mix, check = cell.traffic, cell.config["correct"]
    b, k = mix["B"], check["loss_batches"]
    records = []
    for payload in traffic.token_records(cell.traffic_name, mix, seed, vocab):
        records.append(np.frombuffer(payload, np.int32))
        if len(records) == b * k:
            break
    diffs = []
    for i in range(k):
        toks = np.stack(records[i * b:(i + 1) * b])
        ids, labels = toks[:, :-1], toks[:, 1:]
        exact = float(reference.mean_loss(params, ids, labels))
        low = float(reference.mean_loss(params, ids, labels, quantize=dtype))
        diffs.append(low - exact)
        _say(f"seed {seed} batch {i}: exact {exact:.6f}, rounded "
             f"{low:.6f}, difference {diffs[-1]:+.6f}")
    rms = math.sqrt(sum(d * d for d in diffs) / len(diffs))
    caught = rms > check["loss_tolerance"]
    _say(f"seed {seed}: rms {rms:.6f} over {k} batch(es) against "
         f"{check['loss_tolerance']}: {'caught' if caught else 'NOT caught'}"
         f"; one batch alone would catch "
         f"{sum(abs(d) > check['loss_tolerance'] for d in diffs)} of {k}")
    return caught


def _serve(cell, reference, params, seed, vocab, dtype):
    import numpy as np

    from benchmarks import traffic

    mix, margin = cell.traffic, cell.config["correct"]["logit_margin"]
    _, out_max = traffic.dist_bounds(mix["output"])
    kept, worst_all, over = {}, 0.0, 0
    for client in range(mix["clients"]):
        req = traffic.request(cell.traffic_name, mix, seed, vocab, client, 0)
        if kept.get(req["class"], 0) >= mix["check_per_class"]:
            continue
        kept[req["class"]] = kept.get(req["class"], 0) + 1
        rng = np.random.default_rng([seed, client])
        n_out = req["max_tokens"]
        ids = req["prompt"] + [int(x) for x in
                               rng.integers(vocab, size=n_out - 1)]
        padded = -(-len(ids) // reference.Q_BLOCK) * reference.Q_BLOCK
        at = np.pad(np.arange(len(req["prompt"]) - 1, len(ids)),
                    (0, out_max - n_out), mode="edge")
        seq = np.asarray(ids + [0] * (padded - len(ids)), np.int32)
        exact = np.asarray(reference.logits_at(params, seq, at))[:n_out]
        low = np.asarray(reference.logits_at(params, seq, at,
                                             quantize=dtype))[:n_out]
        gaps = exact.max(axis=-1) - exact[np.arange(n_out),
                                          low.argmax(axis=-1)]
        worst_all = max(worst_all, float(gaps.max()))
        over += int((gaps > margin).sum())
        _say(f"seed {seed} client {client} ({req['class']}, {n_out} "
             f"positions): the rounded reference's tokens are within "
             f"{gaps.max():.4f} of the exact top logit; "
             f"{int((gaps > 0).sum())} differ from the exact top token")
    caught = worst_all > margin
    _say(f"seed {seed}: worst {worst_all:.4f} against the margin {margin} "
         f"({over} positions over it): "
         f"{'caught' if caught else 'NOT caught'}")
    return caught


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dtype", default="float8_e4m3fn")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks import harness
    from dmlc_tpu.models import transformer as tfm

    cell = harness.load_cell(args.workload)
    reference = harness.reference_for(cell.config)
    cfg = tfm.TransformerConfig(**cell.config["model"])
    dtype = getattr(jnp, args.dtype)
    _say(f"REHEARSAL, NOT A RESULT: {args.workload} on "
         f"{jax.devices()[0].platform}, matmul operands rounded to "
         f"{args.dtype}")
    control = {"train": _train, "serve": _serve}[cell.kind]
    caught = []
    for seed in args.seeds:
        params = jax.jit(lambda key: tfm.init_params(key, cfg))(
            jax.random.PRNGKey(seed))
        caught.append(control(cell, reference, params, seed, cfg.vocab,
                              dtype))
    _say(f"caught on {sum(caught)} of {len(caught)} seeds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
