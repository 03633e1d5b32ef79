#!/usr/bin/env python3
"""Language-model training example: byte-level next-token prediction over
the framework's parallelism lanes.

Two flagship configurations, both driven from one script:

  # DP x SP — ring attention for long sequences (seq sharded over "seq")
  python examples/train_lm.py corpus.txt --mesh data=2,seq=4 --seq 2048

  # DP x TP(+MoE) — Megatron splits + top-1 experts via GSPMD
  python examples/train_lm.py corpus.txt --model tp --mesh data=2,model=4

The corpus is any text/binary file; tokens are raw bytes (vocab 256), so
there is no external tokenizer. Windows are sampled deterministically:
each step's GLOBAL batch is seeded by (seed, step) over the whole corpus
and every host takes its contiguous row slice (process_part), so the
global batch stream is identical no matter when the run was resumed —
the elastic data-plane determinism rule (doc/robustness.md), applied to
the example's sampler.

Smoke-testable on CPU:  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/train_lm.py README.md --mesh data=2,seq=4 --seq 256 \
      --steps 3 --embed 32 --layers 1
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def parse_mesh(spec: str):
    """"data=2,seq=4" -> (("data", 2), ("seq", 4))."""
    out = []
    for part in spec.split(","):
        name, _, n = part.partition("=")
        out.append((name.strip(), int(n)))
    return tuple(out)


def load_corpus(path: str, seq: int) -> np.ndarray:
    """The whole corpus, memory-mapped (each host reads only the window
    bytes it samples — no per-host byte-slice copy)."""
    if os.path.getsize(path) < seq + 1:
        raise SystemExit(f"corpus has {os.path.getsize(path)} bytes; "
                         f"need at least seq+1={seq + 1}")
    return np.memmap(path, np.uint8, mode="r")


def byte_windows(data: np.ndarray, seq: int, batch: int, seed: int,
                 step: int, part: int = 0, npart: int = 1) -> np.ndarray:
    """[batch, seq+1] int32 windows for THIS host at `step`.

    The GLOBAL stream of npart*batch windows per step is seeded by
    (seed, step) alone and sampled over the whole corpus — never by which
    host draws it (the elastic data-plane determinism rule,
    doc/robustness.md): a resumed run continues the identical stream from
    any step with no sampler replay, and every host slices its contiguous
    rows out of the same global batch."""
    rng = np.random.default_rng([seed, step])
    starts = rng.integers(0, data.size - seq, size=npart * batch)
    mine = starts[part * batch:(part + 1) * batch]
    return np.stack([np.asarray(data[s:s + seq + 1])
                     for s in mine]).astype(np.int32)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("corpus", help="any file; bytes are the tokens")
    ap.add_argument("--model", default="lm", choices=("lm", "tp"),
                    help="lm: DP x SP ring attention; tp: DP x TP + MoE")
    ap.add_argument("--mesh", default="data=1,seq=1",
                    help='axis spec, e.g. "data=2,seq=4" (lm) or '
                         '"data=2,model=4" (tp)')
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=0,
                    help="rows per step (0 = one per data-axis slice)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--embed", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--experts", type=int, default=0,
                    help="tp only: MoE experts (0 = 2 per model slice)")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default="",
                    help="URI to write params + step each --ckpt-every "
                         "steps (any stream scheme: file/s3/hdfs/azure). "
                         "jax.distributed worlds write a TWO-PHASE job "
                         "checkpoint (per-host parts + rank-0 commit "
                         "marker; torn step sets are unresumable); other "
                         "multi-host runs write one file per host "
                         "(.partK suffix appended). Saving params whose "
                         "model axis spans HOSTS is out of this "
                         "example's scope (shards must be addressable)")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", default="",
                    help="checkpoint URI (same base as --checkpoint) to "
                         "restore before training")
    args = ap.parse_args()
    if args.checkpoint and args.ckpt_every <= 0:
        raise SystemExit("--ckpt-every must be positive")

    import jax
    from jax.sharding import Mesh
    from dmlc_core_tpu.parallel import init_from_env
    from dmlc_core_tpu.tpu.runtime import (compile_report, device_banner,
                                           device_report,
                                           enable_compile_cache)
    from dmlc_core_tpu.tpu.sharding import process_part

    enable_compile_cache()
    init_from_env()  # multi-host: jax.distributed under dmlc-submit

    # elastic-mesh check-in (doc/robustness.md "Elastic mesh training"):
    # under dmlc-submit the worker joins the tracker rendezvous, which
    # opens the heartbeat channel (env-gated) — the abort broadcast and
    # the step watchdog below are what turn a SIGKILL'd peer into a
    # structured between-steps abort instead of a hung collective
    client = assign = None
    if os.environ.get("DMLC_TRACKER_URI"):
        from dmlc_core_tpu.tracker.client import RendezvousClient
        from dmlc_core_tpu.tracker.wire import env_int
        client = RendezvousClient(os.environ["DMLC_TRACKER_URI"],
                                  env_int("DMLC_TRACKER_PORT", 9091))
        assign = client.start(heartbeat=None)

    nproc = jax.process_count()
    axes = parse_mesh(args.mesh)
    need = int(np.prod([n for _, n in axes]))
    # multi-process worlds step over this HOST's mesh and keep replicas
    # identical with a cross-host parameter mean (allreduce_tree below):
    # works on every backend — XLA's CPU floor cannot run multiprocess
    # computations at all (tpu/sharding.host_data_mesh), and on TPU the
    # reduction rides ICI/DCN through the same helper
    devs = jax.local_devices() if nproc > 1 else jax.devices()
    if len(devs) < need:
        raise SystemExit(f"mesh {args.mesh} needs {need} devices, "
                         f"have {len(devs)}")
    mesh = Mesh(np.array(devs[:need]).reshape([n for _, n in axes]),
                tuple(name for name, _ in axes))
    print(device_banner(device_report(mesh)))
    names = dict(axes)
    n_data = names.get("data", 1)
    batch = args.batch or n_data
    if batch % n_data:
        raise SystemExit(f"--batch {batch} must divide by data={n_data}")
    n_seq = names.get("seq", 1)
    if args.seq % n_seq:
        raise SystemExit(f"--seq {args.seq} must divide by seq={n_seq}")

    if args.model == "lm":
        from dmlc_core_tpu.models.transformer import (TransformerConfig,
                                                      TransformerLM)
        cfg = TransformerConfig(vocab=256, max_seq=args.seq,
                                embed=args.embed, heads=args.heads,
                                layers=args.layers)
        model = TransformerLM(cfg, mesh, learning_rate=args.lr)
    else:
        from dmlc_core_tpu.models.tp_transformer import (TPTransformerConfig,
                                                         TPTransformerLM)
        n_model = names.get("model", 1)
        # attention heads shard over the model axis: round up to the next
        # multiple so every (heads, mesh) combination is valid, and say
        # so. The rounded count must still divide --embed (head_dim =
        # embed // heads) — fail with guidance instead of a reshape error
        # deep inside jit.
        heads = -(-args.heads // n_model) * n_model
        if heads != args.heads:
            print(f"note: --heads {args.heads} rounded up to {heads} "
                  f"(must divide by model={n_model})")
        if args.embed % heads:
            raise SystemExit(
                f"--embed {args.embed} must divide by heads={heads} "
                f"(after rounding to the model axis); pick --embed as a "
                f"multiple of {heads}")
        cfg = TPTransformerConfig(
            vocab=256, max_seq=args.seq, embed=args.embed,
            heads=heads, layers=args.layers,
            moe_experts=args.experts or 2 * n_model)
        model = TPTransformerLM(cfg, mesh, learning_rate=args.lr)

    from dmlc_core_tpu.utils import restore_checkpoint, save_checkpoint

    params = model.init(seed=args.seed)
    part, npart = process_part()
    mesh_world = nproc > 1
    # one checkpoint file per host: concurrent writers to a shared URI
    # would clobber each other
    suffix = f".part{part}of{npart}" if npart > 1 else ""
    # the data stream's identity: a resume under a different one would
    # silently continue on different windows (same pattern as train.py)
    identity = {"model": args.model, "mesh": args.mesh,
                "seq": str(args.seq), "batch": str(batch),
                "seed": str(args.seed), "part": f"{part}/{npart}"}
    start = 0
    if args.resume and mesh_world:
        # two-phase job checkpoint: ONLY a committed marker is
        # resumable — a torn step set (some hosts saved step N, others
        # died first) is invisible, and restore falls back to whatever
        # the marker last named. A missing marker (relaunch before the
        # first commit) means a fresh start, which is exactly what a
        # supervised world-relaunch with the original command line
        # needs.
        from dmlc_core_tpu.utils import restore_job_checkpoint
        got = restore_job_checkpoint(args.resume, part, npart,
                                     like=params)
        if got is None:
            print("no committed job checkpoint yet; starting fresh",
                  flush=True)
        else:
            params, start, extra = got
            mismatch = {k: (extra.get(k), v) for k, v in identity.items()
                        if extra.get(k) != v}
            if mismatch:
                raise SystemExit(
                    f"checkpoint was written under a different run "
                    f"identity (stored vs now): {mismatch}")
            print(f"resumed from committed job checkpoint {args.resume} "
                  f"at step {start}", flush=True)
    elif args.resume:
        # restore onto the template's shardings (preemption recovery)
        params, start, extra = restore_checkpoint(args.resume + suffix,
                                                  like=params)
        mismatch = {k: (extra.get(k), v) for k, v in identity.items()
                    if extra.get(k) != v}
        if mismatch:
            raise SystemExit(
                f"checkpoint was written under a different run identity "
                f"(stored vs now): {mismatch}")
        print(f"resumed from {args.resume}{suffix} at step {start}")

    def save_ckpt(at_step):
        if mesh_world:
            from dmlc_core_tpu.parallel import barrier
            from dmlc_core_tpu.utils import (commit_job_checkpoint,
                                             save_job_checkpoint)
            save_job_checkpoint(args.checkpoint, params, at_step,
                                part, npart, extra=identity)
            # every host must have PUBLISHED its part before rank 0
            # names the set in the commit marker; a host that dies
            # before the barrier leaves step at_step torn and therefore
            # unresumable — by design
            barrier(f"ckpt-{at_step}")
            if part == 0:
                commit_job_checkpoint(args.checkpoint, at_step, npart)
        else:
            save_checkpoint(args.checkpoint + suffix, params,
                            step=at_step, extra=identity)

    data = load_corpus(args.corpus, args.seq)
    from dmlc_core_tpu.parallel import (STEP_ABORT_EXIT, StepWatchdog,
                                        allreduce, allreduce_tree,
                                        structured_abort)
    from dmlc_core_tpu.tracker.wire import TrackerAbortedError
    rank = assign.rank if assign is not None else part
    wd = step = None
    first = last = None
    try:
        if mesh_world or os.environ.get("DMLC_TRACKER_URI"):
            wd = StepWatchdog(rank=rank).start()
        for step in range(start, args.steps):
            if wd is not None:
                wd.step_begin(step)
            # per-step seeding: no sampler replay needed on resume —
            # step s draws the same global windows whether or not the
            # run restarted
            w = byte_windows(data, args.seq, batch, args.seed, step,
                             part, npart)
            params, loss = model.step(params, w[:, :-1], w[:, 1:])
            if mesh_world:
                # host-local step + cross-host parameter mean == the
                # global-batch update (equal per-host batches), and the
                # rank-ordered reduction makes every replica (and every
                # rerun of the same schedule) bit-identical
                params = allreduce_tree(params, "mean")
                loss = allreduce(np.asarray(loss, np.float32), "mean")
            if wd is not None:
                wd.step_end()
            last = float(loss)
            if first is None:
                first = last
            print(f"step {step}: loss {last:.4f}", flush=True)
            if args.checkpoint and (step + 1) % args.ckpt_every == 0:
                save_ckpt(step + 1)
        if (args.checkpoint and last is not None
                and args.steps % args.ckpt_every != 0):  # not saved yet
            save_ckpt(args.steps)
    except TrackerAbortedError as e:
        # a peer died: the tracker broadcast the abort and check()
        # surfaced it BETWEEN steps — drain, leave the postmortem
        # record, and exit with the structured code the supervisor maps
        # to "relaunch the world from the last committed checkpoint"
        if wd is not None:
            wd.drain()
        at = f" at step {step}" if step is not None else ""
        structured_abort(f"train_lm{at}: {e}", rank=rank)
        return STEP_ABORT_EXIT
    finally:
        if wd is not None:
            wd.stop()
    if client is not None:
        client.shutdown(rank)
    if last is None:
        print(f"nothing to do: resume step {start} >= --steps {args.steps}")
        return 0
    print("compile: " + json.dumps(compile_report()))
    print(f"done: loss {first:.4f} -> {last:.4f} over steps "
          f"{start}..{args.steps - 1} (mesh {args.mesh}, seq {args.seq}, "
          f"part {part}/{npart})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
