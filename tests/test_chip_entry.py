"""One process per chip, pinned on the CPU: chip_smoke.py's parent stays off
jax, a missing chip or a failed phase is a non-zero exit of chip_smoke.py and
of every cell of benchmarks/run.py, and the persistent compile cache lives
where the contract says."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POISON = "poisoned jax: this process must stay off jax"


@pytest.fixture
def poisoned_env(tmp_path):
    """An environment whose import path answers `import jax` with an
    error — in the parent and in every child it starts."""
    (tmp_path / "poison" / "jax").mkdir(parents=True)
    (tmp_path / "poison" / "jax" / "__init__.py").write_text(
        f"raise ImportError({POISON!r})\n")
    env = dict(os.environ, PYTHONPATH=str(tmp_path / "poison"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def _run(argv, env, timeout=240):
    return subprocess.run([sys.executable] + argv, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


# -- chip_smoke.py ------------------------------------------------------------
def test_chip_smoke_parent_survives_poisoned_jax(poisoned_env):
    """The parent reaches its first child and reports that child's
    failure: had the parent imported jax it would have died on the
    poison itself, with a traceback and no phase name."""
    out = _run(["chip_smoke.py"], poisoned_env)
    assert out.returncode != 0
    assert "chip_smoke: FAILED phase=device" in out.stderr
    assert POISON in out.stderr          # the child's error, relayed
    assert "Traceback" not in out.stderr.split("FAILED phase=device")[0]
    assert '"ok"' not in out.stdout


def test_chip_smoke_without_a_chip_names_phase_and_platform():
    """On a CPU-only machine the first phase fails inside jax (the
    children run with JAX_PLATFORMS=tpu: no fallback), the exit code is
    non-zero and no result line is printed."""
    out = _run(["chip_smoke.py"], dict(os.environ))
    assert out.returncode != 0
    assert "chip_smoke: FAILED phase=device" in out.stderr
    assert "found no 'tpu' platform" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_failed_phase_propagates(tmp_path, monkeypatch, capsys):
    """Any phase's non-zero child ends the run non-zero, names the
    phase, runs no later phase and prints no result."""
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "LOGS", str(tmp_path / "logs"))
    ran = []

    def fine(run):
        ran.append("fine")
        run.device = {"platform": "tpu", "kind": "fake", "count": 1}

    def boom(run):
        ran.append("boom")
        run.child([sys.executable, "-c",
                   "import sys; print('half done'); sys.exit(3)"])

    def never(run):
        ran.append("never")

    rc = chip_smoke.run_phases([("fine", fine), ("boom", boom),
                                ("never", never)])
    io = capsys.readouterr()
    assert rc != 0 and ran == ["fine", "boom"]
    assert "FAILED phase=boom" in io.err and "exit 3" in io.err
    assert '"ok"' not in io.out
    # the child's whole output is kept for the builder
    logs = os.listdir(tmp_path / "logs")
    assert len(logs) == 1 and "boom" in logs[0]
    assert "half done" in (tmp_path / "logs" / logs[0]).read_text()


def test_chip_smoke_failed_check_propagates(tmp_path, monkeypatch, capsys):
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "LOGS", str(tmp_path / "logs"))

    def cpu_found(run):
        chip_smoke.check(False, "jax found platform='cpu', not 'tpu'")

    assert chip_smoke.run_phases([("device", cpu_found)]) != 0
    err = capsys.readouterr().err
    assert "FAILED phase=device" in err and "platform='cpu'" in err


# -- benchmarks/run.py --------------------------------------------------------
def _cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _run_cell(workload):
    return _run(["benchmarks/run.py", "--workload", workload, "--seed", "0",
                 "--seconds", "1", "--trace", "0"],
                dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.parametrize("workload", _cells())
def test_cell_benchmark_refuses_cpu(workload):
    """No CPU number under a device metric's name: every cell of
    BENCHMARK.json, run where jax finds no chip, exits EXIT_NO_CHIP before
    it writes any data, names the platform it found and prints no result."""
    out = _run_cell(workload)
    assert out.returncode == 3, out.stderr[-2000:]
    assert "jax found platform 'cpu'" in out.stderr
    assert out.stdout == ""


def test_cell_benchmark_unknown_workload_prints_no_result():
    out = _run_cell("no-such.cell")
    assert out.returncode != 0
    assert "no-such.cell" in out.stderr
    assert out.stdout == ""


# -- the compile cache --------------------------------------------------------
_SHOW_CACHE = (
    "import jax\n"
    "from dmlc_core_tpu.tpu.runtime import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")


def _show_cache(env, cwd):
    out = subprocess.run([sys.executable, "-c", _SHOW_CACHE], cwd=cwd,
                         env=dict(env, PYTHONPATH=REPO),
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_compile_cache_env_set_means_code_sets_nothing(tmp_path):
    where = str(tmp_path / "elsewhere")
    got = _show_cache(dict(os.environ, JAX_COMPILATION_CACHE_DIR=where),
                      str(tmp_path))
    # jax read the variable itself; the helper set no path and left
    # jax's own caching floor alone
    assert got == [where, where, "1.0"]


def test_compile_cache_default_is_one_fixed_path(tmp_path):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    first = _show_cache(env, str(tmp_path / "a"))
    second = _show_cache(env, str(tmp_path / "b"))
    want = os.path.join(REPO, ".jax_cache")
    assert first == second == [want, want, "0.0"]


_SCOPED = (
    "import sys, jax, jax.numpy as jnp\n"
    "from dmlc_core_tpu.tpu.runtime import enable_compile_cache\n"
    "if sys.argv[2] == 'program': enable_compile_cache()\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
    "def f(x):\n"
    "    with jax.named_scope(sys.argv[1]):\n"
    "        return jnp.sin(x) * 2\n"
    "print(jax.jit(f).lower(jnp.ones(8)).compile().as_text())\n")


@pytest.mark.parametrize("how", ["program", "jax_default"])
def test_cached_executable_carries_the_scopes_of_this_program(tmp_path, how):
    """Two programs that differ in a ``jax.named_scope`` only, one cache:
    with the program's ``enable_compile_cache`` the second compiles anew and
    its operations carry its own scope; by jax's default (the control) the
    second is handed the first's executable, stale names and all."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    texts = []
    for scope in ("first.scope", "second.scope"):
        out = subprocess.run([sys.executable, "-c", _SCOPED, scope, how],
                             cwd=str(tmp_path), env=env, capture_output=True,
                             text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        texts.append(out.stdout)
    assert "first.scope" in texts[0]
    if how == "program":
        assert "second.scope" in texts[1] and "first.scope" not in texts[1]
    else:
        assert "first.scope" in texts[1] and "second.scope" not in texts[1]
