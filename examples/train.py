#!/usr/bin/env python3
"""End-to-end training example: any supported data URI -> HBM-resident
batches -> distributed linear learner -> checkpoint/resume.

Walks the full TPU-native pipeline surface in ~60 lines of user code:

  python examples/train.py data.libsvm --epochs 3
  python examples/train.py "data.libsvm?shuffle_parts=16" --objective pairwise
  python examples/train.py s3://bucket/train.drec --batch-rows 8192
  python examples/train.py data.rec --resume ckpt.bin   # after preemption
  python examples/train.py "day_0.tsv?hash_bits=25" --format criteo --model fm
  python examples/train.py "day_0.tsv?hash_bits=27" --format criteo --model fm \
      --table-layout range_sharded   # w, v cut by key range over the chips

Under dmlc-submit the same script runs per-host with its own partition:

  bin/dmlc-submit --cluster=tpu-pod --host-file hosts.txt -- \
      python examples/train.py hdfs://nn/train.rec

(each worker calls init_from_env + process_part and reads a disjoint,
exactly-covering slice — the reference's distributed-read contract).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json  # noqa: E402

import numpy as np  # noqa: E402

from dmlc_core_tpu import telemetry  # noqa: E402
from dmlc_core_tpu.models import FMLearner, LinearLearner  # noqa: E402
from dmlc_core_tpu.parallel import init_from_env  # noqa: E402
from dmlc_core_tpu.tpu import DeviceRowBlockIter, data_mesh  # noqa: E402
from dmlc_core_tpu.tpu.device_iter import jax_profiler_capture  # noqa: E402
from dmlc_core_tpu.tpu.runtime import (compile_report,  # noqa: E402
                                       device_banner, device_report,
                                       enable_compile_cache)
from dmlc_core_tpu.tpu.sharding import process_part  # noqa: E402
from dmlc_core_tpu.utils import (restore_checkpoint,  # noqa: E402
                                 save_checkpoint)


def _labeled(snap, name: str, label: str) -> dict:
    """{label value: count} of one labeled counter in a snapshot."""
    return {c["labels"][label]: int(c["value"]) for c in snap["counters"]
            if c["name"] == name and c["value"]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("uri", help="libsvm/csv/libfm/criteo/rec/crec/drec data "
                               "URI (file://, s3://, hdfs://, azure://); a "
                               "format's options ride it (?hash_bits=25)")
    ap.add_argument("--format", default="auto",
                    help="data format; auto: ?format= of the URI, the "
                         "suffix of a binary file, else libsvm")
    ap.add_argument("--num-features", type=int, default=0,
                    help="0 = discover from the first epoch's max index")
    ap.add_argument("--model", default="linear", choices=("linear", "fm"),
                    help="linear learner or second-order factorization "
                         "machine (the libfm lane's canonical consumer)")
    ap.add_argument("--fm-rank", type=int, default=8,
                    help="FM interaction-factor rank k")
    ap.add_argument("--table-layout", default="replicated",
                    choices=("replicated", "range_sharded"),
                    help="fm on several devices: every device holds the "
                         "whole tables, or each a contiguous range of their "
                         "rows (a batch's rows pulled from and pushed to "
                         "their owners; --num-features is rounded up to a "
                         "multiple of the devices)")
    ap.add_argument("--objective", default="logistic",
                    choices=("logistic", "squared", "pairwise"))
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-rows", type=int, default=4096)
    ap.add_argument("--learning-rate", type=float, default=0.1)
    ap.add_argument("--checkpoint", default="",
                    help="URI to write the model + data position each epoch")
    ap.add_argument("--resume", default="",
                    help="checkpoint URI to resume from (mid-epoch exact)")
    args = ap.parse_args()

    enable_compile_cache()
    init_from_env()  # multi-host: no-op single-process, rendezvous on pods
    part, npart = process_part()
    mesh = data_mesh()
    device = device_report(mesh)
    print(device_banner(device))

    if args.num_features <= 0:
        # cheap discovery pass over this part only (a real deployment
        # passes --num-features; feature spaces are part-invariant)
        from dmlc_core_tpu.io import NativeParser
        mx = 0
        with NativeParser(args.uri, part=part, npart=npart,
                          fmt=args.format) as p:
            for b in p:
                mx = max(mx, int(b.max_index))
        args.num_features = mx + 1

    if args.model == "fm":
        if args.table_layout == "range_sharded":
            n_dev = int(mesh.devices.size)  # equal ranges: pad the last
            args.num_features = -(-args.num_features // n_dev) * n_dev
        learner = FMLearner(num_features=args.num_features, mesh=mesh,
                            k=args.fm_rank, objective=args.objective,
                            learning_rate=args.learning_rate,
                            table_layout=args.table_layout)
    else:
        learner = LinearLearner(num_features=args.num_features, mesh=mesh,
                                objective=args.objective,
                                learning_rate=args.learning_rate)
    params = learner.init()
    start_epoch = 0
    data_state = None
    if args.resume:
        params, step, extra = restore_checkpoint(args.resume, like=params)
        start_epoch = step
        if "batches_consumed" in extra:
            # the epoch-boundary checkpoint below records 0 batches; a
            # preemption-time checkpoint records the mid-epoch position.
            # Rebuild the state from the SAVED identity (not current CLI
            # args) so restore() can catch a mismatched resume — a batch
            # count under different batch_rows/uri/part is different data.
            data_state = {
                k: int(extra[k]) if k in ("batches_consumed", "batch_rows",
                                          "part", "npart", "epoch")
                else extra[k]
                for k in ("batches_consumed", "batch_rows", "part",
                          "npart", "uri", "fmt", "epoch") if k in extra}

    it = DeviceRowBlockIter(args.uri, part=part, npart=npart, mesh=mesh,
                            fmt=args.format, batch_rows=args.batch_rows,
                            dense_dtype="bf16",
                            col_owners=getattr(learner, "col_owners", (1, 0)))
    epochs = []
    first_batch_devices = None
    shapes = telemetry.gauge("device_distinct_shapes")
    try:
        with jax_profiler_capture():
            for epoch in range(start_epoch, args.epochs):
                if data_state is not None:  # mid-epoch resume, once
                    it.restore(data_state)
                    data_state = None
                losses = []
                rows = 0
                shapes_before = shapes.value
                for batch in it:
                    if first_batch_devices is None:
                        first_batch_devices = {
                            k: sorted(d.id for d in v.devices())
                            for k, v in batch.tree().items()}
                        print("first batch shards on device ids: "
                              f"{first_batch_devices}")
                    params, loss = learner.step(params, batch)
                    losses.append(float(loss))
                    rows += batch.total_rows
                new_shapes = int(shapes.value - shapes_before)
                summary = (f"mean loss {float(np.mean(losses)):.6f} over "
                           f"{len(losses)} batches, {rows} rows, "
                           f"{new_shapes} new batch shapes" if losses
                           else "no batches in this part")
                print(f"epoch {epoch}: {summary}")
                epochs.append({
                    "epoch": epoch, "batches": len(losses), "rows": rows,
                    "mean_loss": float(np.mean(losses)) if losses else None,
                    "new_shapes": new_shapes})
                it.before_first()
                if args.checkpoint:
                    st = {str(k): str(v) for k, v in it.state().items()}
                    save_checkpoint(args.checkpoint, params, step=epoch + 1,
                                    extra=st)
    finally:
        it.close()
    # one machine-readable line: what ran where, what the transfer path
    # did, and what compilation cost (chip_smoke.py reads it)
    snap = telemetry.snapshot(native=False)
    print("summary: " + json.dumps({
        "device": device,
        "first_batch_devices": first_batch_devices,
        "epochs": epochs,
        "zero_copy_batches": int(telemetry.counter(
            "device_zero_copy_batches_total").value),
        "zero_copy_fallbacks": _labeled(
            snap, "device_zero_copy_fallbacks_total", "reason"),
        "recycle_skipped": int(telemetry.gauge(
            "device_recycle_skipped").value),
        "alias_probe": _labeled(snap, "device_alias_probe_total",
                                "verdict"),
        "compile": compile_report()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
