"""Real-subprocess worker driven by test_distributed_real.py.

The reference proves its launch layer with actual separate worker processes
rendezvousing over real sockets (reference tracker/dmlc_tracker/local.py:12-49);
this worker is the TPU-native equivalent: it consumes the cluster=tpu-pod env
protocol (tracker/launchers.py build_tpu_pod_env), initializes
jax.distributed against a real coordination service, shards input with
process_part(), and allreduces shard statistics across OS processes.

Liveness mirror (doc/robustness.md "Distributed job liveness"): when the
launcher also exports DMLC_TRACKER_URI/PORT the worker checks into the
rabit rendezvous and — with DMLC_TRACKER_HEARTBEAT_MS set — holds the
heartbeat channel for the duration of the compute phase, so chaos tests
can SIGKILL a worker and watch the tracker's dead-rank/abort machinery
end-to-end around a real jax.distributed workload.

Usage: python distributed_worker.py <repo_root> <data_path> <out_json>
"""

import json
import os
import sys


def main() -> None:
    repo, data, out = sys.argv[1], sys.argv[2], sys.argv[3]
    sys.path.insert(0, repo)
    # a CPU test topology: several JAX processes on one host cannot
    # share a chip
    os.environ["JAX_PLATFORMS"] = "cpu"

    from dmlc_core_tpu.io.native import NativeParser
    from dmlc_core_tpu.parallel import distributed
    from dmlc_core_tpu.tpu.sharding import process_part

    # optional tracker check-in: heartbeat liveness rides alongside the
    # JAX coordination service when the launcher provides a tracker
    client = assignment = None
    if os.environ.get("DMLC_TRACKER_URI"):
        from dmlc_core_tpu.tracker.client import RendezvousClient
        client = RendezvousClient(os.environ["DMLC_TRACKER_URI"],
                                  int(os.environ["DMLC_TRACKER_PORT"]))
        assignment = client.start()

    distributed.init_from_env()

    part, npart = process_part()
    rows = 0
    label_sum = 0.0
    with NativeParser(data, part=part, npart=npart) as p:
        for b in p:
            rows += b.num_rows
            label_sum += float(b.label.sum())
            if client is not None and client.heartbeat is not None:
                # long compute loops surface the abort broadcast between
                # batches instead of finishing doomed work
                client.heartbeat.check()

    total_rows = int(distributed.allreduce(rows))
    total_label = float(distributed.allreduce(label_sum))
    max_rows = int(distributed.allreduce(rows, op="max"))
    # broadcast: every process must end up with root's value
    bcast = int(distributed.broadcast(distributed.rank() * 100 + 7, root=0))

    with open(out, "w") as f:
        json.dump({
            "rank": distributed.rank(),
            "world": distributed.world_size(),
            "part": part,
            "npart": npart,
            "local_rows": rows,
            "total_rows": total_rows,
            "total_label": total_label,
            "max_rows": max_rows,
            "bcast": bcast,
        }, f)

    if client is not None and assignment is not None:
        client.shutdown(assignment.rank)


if __name__ == "__main__":
    main()
