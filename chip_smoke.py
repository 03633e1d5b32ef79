#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the data path, the trainers, the scoring server and the kernel once,
through the entry points a user would call, at full width, and checks what
comes out by the repo's own means. Run it where jax finds a TPU:

    python3 chip_smoke.py            # one chip, or every chip of one host

One process per chip: this parent never imports jax (nor any module that
does). It builds the native core, writes its data from a seed, and runs
each phase as a child, one after another, each child gone before the next
starts and each with ``JAX_PLATFORMS=tpu`` so that a machine without a chip
fails inside jax instead of falling back to the CPU. Any phase's non-zero
exit, timeout or failed check ends the run non-zero, naming the phase; no
result line is printed then. On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases: device (what jax finds), build (``make -C cpp`` from scratch),
data (200,000 x 28 libsvm from a seed, converted to .crec and .drec),
trainer (examples/train.py: two epochs + checkpoint, one resume, one epoch
each over .crec — under the jax profiler — and .drec), server (python -m
dmlc_core_tpu.serving at 16,384 features, a few POST /score per row bucket
against a NumPy reference, SIGTERM drain), lm (examples/train_lm.py at its
default width, five steps), kernel (the Pallas CSR->dense kernel compiled
by Mosaic against the XLA scatter, alone and inside the DP step's
shard_map), and on a host with four chips or more, dryrun
(__graft_entry__.dryrun_multichip on the real devices).

Data, checkpoints and the profile live under ``.chip_smoke/``; every
child's full output goes to ``chiprun_out/chip_smoke/``.
"""

import glob
import http.client
import json
import os
import select
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")
LOGS = os.path.join(REPO, "chiprun_out", "chip_smoke")

PLATFORM = "tpu"           # what every child must run on
ROWS, FEATURES = 200_000, 28
SERVE_FEATURES = 1 << 14
SERVE_BUCKETS = (16, 64, 256, 1024)   # the server's default ladder
SERVE_NNZ_PER_ROW = 16     # the density the server warms its ladder to
BUDGET_S = 1150.0          # the whole run, compilation included
SEED = 21


class PhaseFailed(Exception):
    """One phase's child exited non-zero, timed out, or failed a check."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(f"check failed: {what}")


class Run:
    """What the phases share: the deadline, the device the first phase
    found, and per-phase compile totals."""

    def __init__(self):
        self.deadline = time.monotonic() + BUDGET_S
        self.device = None      # {"platform", "kind", "count"}
        self.phase = ""
        self.compiles = {}      # phase -> [backend_compiles, seconds]
        self.cache_dir = None
        self._nlogs = 0

    def log_path(self) -> str:
        self._nlogs += 1
        return os.path.join(LOGS, f"{self._nlogs:02d}-{self.phase}.log")

    def remaining(self) -> float:
        return self.deadline - time.monotonic()

    def child(self, argv, env=None, timeout=600.0) -> str:
        """Run one child to its end and return its stdout; the full
        output goes to the log directory."""
        timeout = min(timeout, self.remaining())
        if timeout <= 0:
            raise PhaseFailed(f"the {BUDGET_S:.0f}s budget is spent")
        full_env = dict(os.environ, JAX_PLATFORMS=PLATFORM)
        full_env.update(env or {})
        log = self.log_path()
        try:
            proc = subprocess.run(argv, cwd=REPO, env=full_env, text=True,
                                  capture_output=True, timeout=timeout)
        except subprocess.TimeoutExpired as e:
            with open(log, "w") as f:
                f.write(f"$ {' '.join(argv)}\nTIMEOUT {timeout:.0f}s\n"
                        f"{e.stdout or ''}\n{e.stderr or ''}")
            raise PhaseFailed(f"timed out after {timeout:.0f}s: "
                              f"{' '.join(argv)}")
        with open(log, "w") as f:
            f.write(f"$ {' '.join(argv)}\nexit {proc.returncode}\n"
                    f"--- stdout\n{proc.stdout}\n--- stderr\n{proc.stderr}")
        if proc.returncode != 0:
            raise PhaseFailed(
                f"exit {proc.returncode}: {' '.join(argv)}\n"
                + "\n".join((proc.stderr or proc.stdout).splitlines()[-15:]))
        return proc.stdout

    def check_device(self, report: dict) -> None:
        """A child's device report must name the chip the first phase
        found — never another platform, never fewer devices."""
        check(report.get("platform") == self.device["platform"]
              and report.get("device_kind") == self.device["kind"]
              and report.get("device_count") == self.device["count"],
              f"child ran on {report}, expected {self.device}")

    def add_compiles(self, report: dict) -> None:
        tot = self.compiles.setdefault(self.phase, [0, 0.0])
        tot[0] += report["backend_compiles"]
        tot[1] += report["compile_seconds"]
        self.cache_dir = report["cache_dir"]


def tagged_json(stdout: str, tag: str) -> dict:
    """The JSON object on the last stdout line that starts with `tag`."""
    for line in reversed(stdout.splitlines()):
        if line.startswith(tag):
            return json.loads(line[len(tag):])
    raise PhaseFailed(f"child printed no {tag!r} line")


# -- phases ------------------------------------------------------------------

def phase_device(run: Run) -> None:
    out = run.child([sys.executable, os.path.abspath(__file__),
                     "--child", "device"], timeout=300)
    dev = tagged_json(out, "device: ")
    check(dev["platform"] == PLATFORM,
          f"jax found platform={dev['platform']!r}, not {PLATFORM!r}")
    run.device = dev


def phase_build(run: Run) -> None:
    for so in glob.glob(os.path.join(REPO, "dmlc_core_tpu", "_native",
                                     "*.so")):
        os.remove(so)
    run.child(["make", "-C", os.path.join(REPO, "cpp")], timeout=900)
    check(os.path.exists(os.path.join(
        REPO, "dmlc_core_tpu", "_native", "libdmlc_core_tpu.so")),
        "make left no libdmlc_core_tpu.so")


def higgs_chunks(rows: int, features: int, seed: int):
    """(x, y) chunks of HIGGS-shaped rows — every feature present — whose
    labels follow a noisy linear rule, so that a falling loss means
    something. The same seed gives the same rows every time."""
    import numpy as np
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=features)
    for start in range(0, rows, 10000):
        n = min(10000, rows - start)
        x = rng.uniform(-3, 3, size=(n, features))
        yield x, x @ w_true + rng.normal(scale=2.0, size=n) > 0


def write_libsvm(path: str, rows: int, features: int, seed: int) -> None:
    fmt = "%d " + " ".join(f"{j}:%.6f" for j in range(features)) + "\n"
    with open(path, "w") as f:
        for x, y in higgs_chunks(rows, features, seed):
            f.write("".join(fmt % (y[i], *x[i].tolist())
                            for i in range(len(y))))


def reference_epoch_loss(rows: int, features: int, seed: int,
                         batch_rows: int = 4096, lr: float = 0.1) -> float:
    """Mean batch loss of the first epoch of examples/train.py's defaults
    (logistic loss, plain SGD from zero, the global batch whatever the
    device count), in float64 NumPy."""
    import numpy as np
    chunks = list(higgs_chunks(rows, features, seed))
    x = np.concatenate([c[0] for c in chunks])
    y = np.concatenate([c[1] for c in chunks]).astype(np.float64)
    w, b, losses = np.zeros(features), 0.0, []
    for lo in range(0, rows, batch_rows):
        xb, yb = x[lo:lo + batch_rows], y[lo:lo + batch_rows]
        m = xb @ w + b
        losses.append(np.mean(np.maximum(m, 0) - m * yb
                              + np.log1p(np.exp(-np.abs(m)))))
        g = 1.0 / (1.0 + np.exp(-m)) - yb
        w -= lr * (xb.T @ g) / len(yb)
        b -= lr * g.mean()
    return float(np.mean(losses))


def phase_data(run: Run) -> None:
    src = os.path.join(WORK, "higgs.libsvm")
    write_libsvm(src, ROWS, FEATURES, SEED)
    for suffix in ("crec", "drec"):   # .drec defaults to bf16
        out = run.child([sys.executable, "-m", "dmlc_core_tpu.io.convert",
                         src, os.path.join(WORK, f"higgs.{suffix}")])
        check(f"wrote {ROWS} rows" in out,
              f".{suffix} conversion wrote {out.strip()!r}, not {ROWS} rows")


def train(run: Run, uri: str, *flags, env=None) -> dict:
    out = run.child([sys.executable, os.path.join("examples", "train.py"),
                     os.path.join(WORK, uri), "--num-features",
                     str(FEATURES), *flags], env=env)
    s = tagged_json(out, "summary: ")
    run.check_device(s["device"])
    run.add_compiles(s["compile"])
    for e in s["epochs"]:
        check(e["rows"] == ROWS, f"{uri} epoch {e['epoch']} saw "
              f"{e['rows']} rows of {ROWS} written")
        check(e["mean_loss"] is not None and 0 < e["mean_loss"] < 10,
              f"{uri} epoch {e['epoch']} loss {e['mean_loss']}")
    for leaf, ids in s["first_batch_devices"].items():
        check(len(set(ids)) == run.device["count"],
              f"{uri} batch leaf {leaf!r} sits on devices {ids}, not on "
              f"{run.device['count']} distinct ones")
    print(f"  {uri} {' '.join(flags)}: loss "
          + " -> ".join(f"{e['mean_loss']:.4f}" for e in s["epochs"])
          + f"; shards on {s['first_batch_devices']}; zero-copy batches "
          f"{s['zero_copy_batches']}, device_zero_copy_fallbacks_total "
          f"{s['zero_copy_fallbacks']}, device_recycle_skipped "
          f"{s['recycle_skipped']}, alias probe {s['alias_probe']}")
    return s


def phase_trainer(run: Run) -> None:
    ckpt = os.path.join(WORK, "linear.ckpt")
    first = train(run, "higgs.libsvm", "--epochs", "2",
                  "--checkpoint", ckpt)
    e0, e1 = first["epochs"]
    # the data-parallel step is the global-batch step: one chip or four,
    # the epoch must follow the single-process NumPy reference (the lane
    # lands bf16 values, hence the tolerance)
    want = reference_epoch_loss(ROWS, FEATURES, SEED)
    check(abs(e0["mean_loss"] - want) <= 0.01 * want,
          f"first-epoch loss {e0['mean_loss']} is not within 1% of the "
          f"NumPy reference {want}")
    print(f"  first-epoch loss {e0['mean_loss']:.6f} vs NumPy reference "
          f"{want:.6f}")
    check(e1["mean_loss"] < e0["mean_loss"],
          f"loss did not fall: {e0['mean_loss']} -> {e1['mean_loss']}")
    check(e1["new_shapes"] == 0,
          f"epoch 2 met {e1['new_shapes']} new batch shapes")
    resumed = train(run, "higgs.libsvm", "--epochs", "3",
                    "--resume", ckpt)["epochs"]
    check([e["epoch"] for e in resumed] == [2],
          f"--resume ran epochs {[e['epoch'] for e in resumed]}, not [2]")
    check(resumed[0]["mean_loss"] < e0["mean_loss"]
          and resumed[0]["mean_loss"] <= e1["mean_loss"] * 1.01,
          f"resumed epoch loss {resumed[0]['mean_loss']} does not continue "
          f"{e0['mean_loss']} -> {e1['mean_loss']}")
    # the same rows through the two binary lanes: one epoch each must
    # match the text lane's first epoch (bf16 against f32 values)
    profile = os.path.join(WORK, "profile")
    crec = train(run, "higgs.crec", "--epochs", "1",
                 env={"DMLC_JAX_PROFILE": profile})["epochs"][0]
    drec = train(run, "higgs.drec", "--epochs", "1")["epochs"][0]
    for name, e in (("crec", crec), ("drec", drec)):
        check(abs(e["mean_loss"] - e0["mean_loss"]) <= 0.02 * e0["mean_loss"],
              f".{name} epoch loss {e['mean_loss']} is not within 2% of "
              f"the libsvm lane's {e0['mean_loss']}")
    traces = glob.glob(os.path.join(profile, "**", "*.xplane.pb"),
                       recursive=True)
    check(bool(traces) and os.path.getsize(traces[0]) > 0,
          f"the profiled run left no .xplane.pb under {profile}")
    print(f"  profile: {os.path.relpath(traces[0], REPO)} "
          f"({os.path.getsize(traces[0])} bytes)")


def score_request(rng, rows: int, w, b):
    """(libsvm payload, reference scores) for `rows` rows at the density
    the server warms to. The reference reads the values back from the
    text, as the server does."""
    import numpy as np
    lines, want = [], []
    for _ in range(rows):
        cols = np.sort(rng.choice(SERVE_FEATURES, SERVE_NNZ_PER_ROW,
                                  replace=False))
        text = [f"{v:.6f}" for v in rng.uniform(-1, 1, cols.size)]
        lines.append("0 " + " ".join(f"{c}:{t}" for c, t in zip(cols, text)))
        margin = float(np.dot(w[cols].astype(np.float64),
                              np.array(text, np.float64)) + b)
        want.append(1.0 / (1.0 + np.exp(-margin)))
    return ("\n".join(lines) + "\n").encode(), np.asarray(want)


def scrape(port: int, name: str) -> float:
    """One metric off /metrics, label series summed (0.0 when absent)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    return sum(float(line.split()[-1]) for line in text.splitlines()
               if line.startswith((name + " ", name + "{")))


def phase_server(run: Run) -> None:
    import numpy as np
    rng = np.random.default_rng(SEED)
    w = rng.normal(size=SERVE_FEATURES).astype(np.float32)
    b = np.float32(0.25)
    model = os.path.join(WORK, "serve.ckpt")
    np.save(os.path.join(WORK, "serve_w.npy"), w)
    # the artifact is written by a child: the checkpoint layer imports jax
    run.child([sys.executable, os.path.abspath(__file__), "--child",
               "save-model", model, str(float(b))])

    log = run.log_path()
    with open(log, "w") as errlog:
        server = subprocess.Popen(
            [sys.executable, "-m", "dmlc_core_tpu.serving",
             "--model-uri", model], cwd=REPO, text=True,
            stdout=subprocess.PIPE, stderr=errlog,
            env=dict(os.environ, JAX_PLATFORMS=PLATFORM))
    try:
        # ready means the bucket ladder has compiled: wait for the one
        # line, or the server's exit, or the budget
        line = ""
        while server.poll() is None and run.remaining() > 0:
            if select.select([server.stdout], [], [], 1.0)[0]:
                line = server.stdout.readline()
                break
        if not line.startswith("SERVE_READY"):
            with open(log) as f:
                tail = "\n".join(f.read().splitlines()[-15:])
            raise PhaseFailed(f"no SERVE_READY (server exit code "
                              f"{server.poll()}):\n{tail}")
        print(f"  {line.strip()}")
        check(f"platform={PLATFORM} " in line,
              f"ready line names another platform: {line.strip()!r}")
        port = int(line.split("port=")[1].split()[0])
        shapes_ready = scrape(port, "serve_distinct_shapes")

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        worst = 0.0
        for bucket in SERVE_BUCKETS:
            for rows in (bucket, bucket // 2 + 1, bucket):
                body, want = score_request(rng, rows, w, b)
                conn.request("POST", "/score", body,
                             {"Content-Type": "application/x-libsvm"})
                resp = conn.getresponse()
                raw = resp.read()
                check(resp.status == 200, f"POST /score ({rows} rows) -> "
                      f"{resp.status} {raw[:200]!r}")
                got = np.asarray(json.loads(raw)["scores"])
                check(got.shape == want.shape and np.isfinite(got).all(),
                      f"{rows} rows scored to shape {got.shape}")
                worst = max(worst, float(np.abs(got - want).max()))
        check(worst <= 1e-4, f"scores differ from the NumPy reference by "
                             f"{worst:.2e}")
        conn.request("GET", "/statz")
        statz = json.loads(conn.getresponse().read())
        conn.close()
        run.check_device(statz["device"])
        run.add_compiles(statz["compile"])
        shapes_after = scrape(port, "serve_distinct_shapes")
        check(shapes_after == shapes_ready,
              f"traffic inside the warmed ladder compiled "
              f"{shapes_after - shapes_ready:.0f} new shapes after ready")
        for bad in ("serve_shed_total", "serve_errors_total",
                    "slo_page_trips_total"):
            check(scrape(port, bad) == 0, f"{bad} = {scrape(port, bad)}")
        server.send_signal(signal.SIGTERM)
        check(server.wait(60) == 0,
              f"server exited {server.returncode} on SIGTERM")
        print(f"  {3 * len(SERVE_BUCKETS)} requests over buckets "
              f"{SERVE_BUCKETS}: all 200, max |score - reference| "
              f"{worst:.2e}; {shapes_ready:.0f} shapes compiled before "
              f"ready, 0 after; /statz device {statz['device']}; SIGTERM "
              f"drained, exit 0")
    finally:
        if server.poll() is None:
            server.kill()
            server.wait(30)


def phase_lm(run: Run) -> None:
    mesh = "data=2,seq=2" if run.device["count"] >= 4 else "data=1,seq=1"
    out = run.child([sys.executable, os.path.join("examples", "train_lm.py"),
                     "README.md", "--steps", "5", "--mesh", mesh])
    check(f"platform={PLATFORM} " in out, "train_lm.py named no "
          f"platform={PLATFORM}")
    run.add_compiles(tagged_json(out, "compile: "))
    done = [ln for ln in out.splitlines() if ln.startswith("done: loss ")]
    check(bool(done), "train_lm.py printed no `done:` line")
    first, last = (float(x) for x in
                   done[0].split("done: loss ")[1].split(" over")[0]
                   .split(" -> "))
    check(last < first, f"LM loss did not fall: {first} -> {last}")
    print(f"  {done[0]}")


def phase_kernel(run: Run) -> None:
    out = run.child([sys.executable, os.path.abspath(__file__),
                     "--child", "kernel",
                     os.path.join(WORK, "higgs.libsvm")])
    k = tagged_json(out, "kernel: ")
    run.check_device(k["device"])
    run.add_compiles(k["compile"])
    print(f"  pallas == xla scatter at 1024x28 nnz 28672 (max abs err "
          f"{k['max_abs_err']:.1e}); DP step over mesh {k['device']['mesh']} "
          f"with the kernel in its shard_map: loss {k['dp_loss_pallas']:.6f}"
          f" == xla scatter {k['dp_loss_xla']:.6f}")


DRYRUN_STAGES = ("one DP step ok", "composed pipeline ok",
                 "binary lanes ok", "crec->FM ok", "mid-epoch resume ok",
                 "DPxSP transformer ok", "DPxTPxEP ok", "PP ok",
                 "pallas ok")


def phase_dryrun(run: Run) -> None:
    n = run.device["count"]
    if n < 4:
        print("  skipped: dryrun_multichip is the four-chip host's phase")
        return
    out = run.child([sys.executable, "-c",
                     "import __graft_entry__ as g; "
                     f"g.dryrun_multichip({n})"], timeout=900)
    check(f"platform={PLATFORM} " in out,
          f"dryrun named no platform={PLATFORM}")
    for stage in DRYRUN_STAGES:
        check(stage in out, f"dryrun_multichip({n}) never printed "
                            f"{stage!r}")
    run.add_compiles(tagged_json(out, "compile: "))
    for ln in out.splitlines():
        print(f"  {ln}")


PHASES = (("device", phase_device), ("build", phase_build),
          ("data", phase_data), ("trainer", phase_trainer),
          ("server", phase_server), ("lm", phase_lm),
          ("kernel", phase_kernel), ("dryrun", phase_dryrun))


def run_phases(phases) -> int:
    """Run `phases` in order; 0 when all passed. The first failure ends
    the run and names its phase."""
    shutil.rmtree(WORK, ignore_errors=True)
    shutil.rmtree(LOGS, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(LOGS)
    run = Run()
    for name, fn in phases:
        run.phase = name
        t0 = time.monotonic()
        print(f"phase {name} ...", flush=True)
        try:
            fn(run)
        except PhaseFailed as e:
            print(f"chip_smoke: FAILED phase={name}: {e}", file=sys.stderr)
            return 1
        dev = run.device or {}
        compiles, seconds = run.compiles.get(name, (None, None))
        print(f"phase {name} ok in {time.monotonic() - t0:.1f}s: "
              f"platform={dev.get('platform')} "
              f"device_kind={dev.get('kind')!r} count={dev.get('count')}"
              + ("" if compiles is None else
                 f" backend_compiles={compiles} compile_seconds="
                 f"{seconds:.1f} compile_cache={run.cache_dir}"),
              flush=True)
    print(json.dumps({"ok": True, "device": run.device}))
    return 0


# -- children (these do import jax) -----------------------------------------

def child_device() -> None:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"jax found no {PLATFORM!r} platform on this "
                         f"machine (JAX_PLATFORMS="
                         f"{os.environ.get('JAX_PLATFORMS')}): {e}")
    print("device: " + json.dumps({"platform": devs[0].platform,
                                   "kind": devs[0].device_kind,
                                   "count": len(devs)}))


def child_save_model(uri: str, bias: str) -> None:
    import numpy as np
    from dmlc_core_tpu.serving.model import save_model
    w = np.load(os.path.join(WORK, "serve_w.npy"))
    save_model(uri, "linear", {"w": w, "b": np.float32(bias)}, w.size)


def child_kernel(libsvm: str) -> None:
    """The Pallas kernel compiled by Mosaic: against the XLA scatter at
    1024 x 28 alone, then inside the dense-margin DP step's
    shard_map over real text batches of 1024 rows x 28 features per
    shard, against the same step formatting with the XLA scatter."""
    import numpy as np
    import jax
    from dmlc_core_tpu.models.linear import LinearLearner
    from dmlc_core_tpu.ops.pallas_kernels import csr_to_dense_pallas
    from dmlc_core_tpu.ops.sparse import csr_to_dense
    from dmlc_core_tpu.tpu import DeviceRowBlockIter, data_mesh
    from dmlc_core_tpu.tpu.runtime import (compile_report, device_report,
                                           enable_compile_cache)
    enable_compile_cache()
    mesh = data_mesh()
    device = device_report(mesh)
    R, F = 1024, 28
    rng = np.random.default_rng(11)
    row = np.repeat(np.arange(R, dtype=np.int32), F)
    col = rng.integers(0, F, R * F).astype(np.int32)
    val = rng.normal(size=R * F).astype(np.float32)
    got = jax.jit(lambda r, c, v: csr_to_dense_pallas(r, c, v, R, F))(
        row, col, val)
    want = jax.jit(lambda r, c, v: csr_to_dense(r, c, v, R, F, impl="xla"))(
        row, col, val)
    err = float(np.abs(np.asarray(got) - np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    def dp_steps(impl: str):
        # the switch is read when the step traces; each learner traces
        # its own
        os.environ["DCT_CSR_TO_DENSE"] = impl
        learner = LinearLearner(F, mesh=mesh, learning_rate=0.1,
                                margin_path="dense")
        params = learner.init()
        with DeviceRowBlockIter(libsvm, mesh=mesh, layout="csr",
                                batch_rows=R * mesh.devices.size) as it:
            for _, batch in zip(range(4), it):
                params, loss = learner.step(params, batch)
        return float(loss), np.asarray(params.w)

    loss_xla, w_xla = dp_steps("xla")
    loss_pl, w_pl = dp_steps("pallas")
    np.testing.assert_allclose(loss_pl, loss_xla, rtol=1e-5)
    np.testing.assert_allclose(w_pl, w_xla, rtol=1e-5, atol=1e-6)
    print("kernel: " + json.dumps({
        "device": device, "max_abs_err": err, "dp_loss_pallas": loss_pl,
        "dp_loss_xla": loss_xla, "compile": compile_report()}))


def main(argv) -> int:
    if argv[:1] == ["--child"]:
        sys.path.insert(0, REPO)
        {"device": child_device, "save-model": child_save_model,
         "kernel": child_kernel}[argv[1]](*argv[2:])
        return 0
    return run_phases(PHASES)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
