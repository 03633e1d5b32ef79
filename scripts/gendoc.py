#!/usr/bin/env python3
"""Doc lane: render doc/ pages from the live package, warnings-as-errors.

The reference builds its docs with doxygen warnings promoted to errors
(reference Makefile:93-97) and hand-maintains doc/parameter.md; here the
pages are GENERATED — the native format registry renders its own parameter
tables (cpp/src/capi.cc dct_parser_formats_doc) and the Python API pages
come from live introspection, so they cannot drift from the code. Any
public symbol without a docstring fails the build (`make doc` in ci).
"""

import importlib
import inspect
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import contracts  # noqa: E402 (shared contract extraction, doc/analysis.md)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "doc")

# the public Python surface, grouped as the index presents it
MODULE_GROUPS = [
    ("Foundation", [
        "dmlc_core_tpu.base",
        "dmlc_core_tpu.params",
        "dmlc_core_tpu.registry",
        "dmlc_core_tpu.config",
        "dmlc_core_tpu.serializer",
        "dmlc_core_tpu.telemetry",
    ]),
    ("Data & I/O", [
        "dmlc_core_tpu.data",
        "dmlc_core_tpu.data.criteo",
        "dmlc_core_tpu.io.native",
        "dmlc_core_tpu.io.convert",
        "dmlc_core_tpu.io.tls_proxy",
    ]),
    ("TPU device bridge", [
        "dmlc_core_tpu.tpu.device_iter",
        "dmlc_core_tpu.tpu.runtime",
        "dmlc_core_tpu.tpu.sharding",
    ]),
    ("Ops & models", [
        "dmlc_core_tpu.ops.sparse",
        "dmlc_core_tpu.ops.attention",
        "dmlc_core_tpu.ops.ranking",
        "dmlc_core_tpu.ops.pallas_kernels",
        "dmlc_core_tpu.models.linear",
        "dmlc_core_tpu.models.fm",
        "dmlc_core_tpu.models.transformer",
        "dmlc_core_tpu.models.tp_transformer",
    ]),
    ("Parallelism & communication", [
        "dmlc_core_tpu.parallel.ring",
        "dmlc_core_tpu.parallel.pipeline_parallel",
        "dmlc_core_tpu.parallel.distributed",
        "dmlc_core_tpu.parallel.varying",
    ]),
    ("Distributed launch", [
        "dmlc_core_tpu.tracker.submit",
        "dmlc_core_tpu.tracker.opts",
        "dmlc_core_tpu.tracker.rendezvous",
        "dmlc_core_tpu.tracker.topology",
        "dmlc_core_tpu.tracker.wire",
        "dmlc_core_tpu.tracker.launchers",
        "dmlc_core_tpu.tracker.bootstrap",
        "dmlc_core_tpu.tracker.supervisor",
        "dmlc_core_tpu.tracker.client",
        "dmlc_core_tpu.tracker.mesos_status",
        "dmlc_core_tpu.tracker.minihttp",
    ]),
    ("Online scoring", [
        "dmlc_core_tpu.serving.server",
        "dmlc_core_tpu.serving.model",
        "dmlc_core_tpu.serving.batching",
        "dmlc_core_tpu.serving.frontend",
    ]),
    ("Utilities", [
        "dmlc_core_tpu.utils.checkpoint",
        "dmlc_core_tpu.utils.fs_fault",
        "dmlc_core_tpu.utils.timer",
    ]),
]

warnings = []


def warn(msg: str) -> None:
    warnings.append(msg)
    print(f"doc warning: {msg}", file=sys.stderr)


def first_paragraph(doc) -> str:
    if not doc:
        return ""
    return inspect.cleandoc(doc).split("\n\n")[0].replace("\n", " ")


def signature_of(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    # non-literal defaults repr with memory addresses
    # ("<function f at 0x7f...>"); sanitize so regeneration is
    # deterministic and the doc lane stays churn-free
    return re.sub(r"<([\w.]+)[^<>]* at 0x[0-9a-f]+>", r"<\1>", sig)


def public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return [n for n, v in vars(mod).items()
            if not n.startswith("_")
            and (inspect.isclass(v) or inspect.isfunction(v))
            and getattr(v, "__module__", None) == mod.__name__]


def render_module(modname: str) -> str:
    mod = importlib.import_module(modname)
    out = [f"## `{modname}`", ""]
    if not mod.__doc__:
        warn(f"{modname}: module has no docstring")
    else:
        out += [first_paragraph(mod.__doc__), ""]
    for name in public_names(mod):
        obj = getattr(mod, name, None)
        if obj is None:
            warn(f"{modname}.{name}: listed in __all__ but missing")
            continue
        if inspect.isclass(obj):
            out.append(f"### class `{name}{signature_of(obj)}`")
            out.append("")
            if not obj.__doc__:
                warn(f"{modname}.{name}: class has no docstring")
            else:
                out += [first_paragraph(obj.__doc__), ""]
            # walk the MRO so inherited public API (e.g. the shared
            # DataParallelModel.step harness) documents on every learner;
            # only project-defined bases contribute (never object/etc.)
            members = {}
            for klass in reversed(obj.__mro__):
                if klass.__module__.startswith("dmlc_core_tpu"):
                    members.update(vars(klass))
            for mname, meth in sorted(members.items()):
                if mname.startswith("_"):
                    continue
                # unwrap BEFORE the callable test: classmethod objects are
                # not callable themselves (pre-3.10 semantics kept)
                if isinstance(meth, (staticmethod, classmethod)):
                    meth = meth.__func__
                if not callable(meth):
                    continue
                doc = first_paragraph(getattr(meth, "__doc__", ""))
                if not doc:
                    warn(f"{modname}.{name}.{mname}: method has no "
                         f"docstring")
                out.append(f"- `{mname}{signature_of(meth)}` — {doc}")
            out.append("")
        elif inspect.isfunction(obj):
            out.append(f"### `{name}{signature_of(obj)}`")
            out.append("")
            if not obj.__doc__:
                warn(f"{modname}.{name}: function has no docstring")
            else:
                out += [first_paragraph(obj.__doc__), ""]
        # plain constants need no entry
    return "\n".join(out)


def gen_api() -> str:
    parts = ["# dmlc_core_tpu Python API",
             "",
             "Generated by `scripts/gendoc.py` — do not edit by hand; "
             "`make doc` regenerates and fails on undocumented public "
             "symbols.",
             ""]
    for group, mods in MODULE_GROUPS:
        parts += [f"# {group}", ""]
        for m in mods:
            parts.append(render_module(m))
            parts.append("")
    return "\n".join(parts)


def rule_doc(cls) -> str:
    """The part of ``cls``'s docstring from "The update rule" on, as
    markdown: its literal block (after ``::``) fenced, prose joined."""
    import inspect
    doc = inspect.getdoc(cls)
    doc = doc[doc.index("The update rule"):]
    head, _, rest = doc.partition("::\n\n")
    lines = rest.splitlines()
    n = 0
    while n < len(lines) and (lines[n].startswith("    ") or not lines[n]):
        n += 1
    block = [line[4:] for line in lines[:n]]
    while block and not block[-1]:
        block.pop()
    return "\n".join([" ".join(head.split()) + ":", "", "```",
                      *block, "```", "", " ".join(
                          " ".join(lines[n:]).split())])


def gen_parameters() -> str:
    import inspect

    from dmlc_core_tpu.io.native import parser_formats_doc
    from dmlc_core_tpu.models import FMLearner
    from dmlc_core_tpu.params import Parameter, field

    class _Example(Parameter):
        """doc example"""
        learning_rate = field(float, default=0.01,
                              desc="step size", lower_bound=0.0)
        num_hidden = field(int, default=128, desc="hidden units")

    return "\n".join([
        "# Parameters",
        "",
        "Generated by `scripts/gendoc.py` from the live registries.",
        "",
        "Both cores carry the same reflection machinery the reference "
        "documents in doc/parameter.md: C++ `Parameter<T>` structs "
        "(cpp/src/parameter.h) drive the native parsers, and the Python "
        "mirror (`dmlc_core_tpu.params.Parameter`) serves configs, with "
        "typed fields, defaults, ranges, enums, and generated docstrings.",
        "",
        "## Declaring parameters (Python)",
        "",
        "```python",
        "from dmlc_core_tpu.params import Parameter, field",
        "",
        "class Example(Parameter):",
        "    learning_rate = field(float, default=0.01, desc='step size',",
        "                          lower_bound=0.0)",
        "    num_hidden = field(int, default=128, desc='hidden units')",
        "```",
        "",
        "`Example().init({...})` validates + coerces; unknown or "
        "out-of-range keys raise with the generated docstring:",
        "",
        "```",
        _Example.docstring(),
        "```",
        "",
        "# Native data formats",
        "",
        "Formats resolve by name through the native registry "
        "(`cpp/src/registry.h`); `?format=` URI arguments or the `fmt` "
        "argument select one; `.rec`/`.drec` files are auto-detected by "
        "suffix.",
        "",
        "URI sugar shared by every format: `#cachefile=<dir>` opts into "
        "the transcoding shard cache — epoch 1 parses text and tees "
        "binary shards, epoch 2+ replays them zero-copy via mmap "
        "([caching.md](caching.md)); a legacy `#<path>` fragment selects "
        "the single-file row-block cache; and "
        "`?shuffle_parts=K[&shuffle_seed=S]` subdivides each partition "
        "into K byte ranges visited in a freshly shuffled order every "
        "epoch (the coarse-grained training shuffle, reference "
        "input_split_shuffle.h).",
        "",
        parser_formats_doc().rstrip(),
        "",
        "# Model table layouts",
        "",
        "`FMLearner(..., table_layout=)`, from the entry point "
        "`examples/train.py --model fm --table-layout "
        "{replicated,range_sharded}`. " + " ".join(
            inspect.getdoc(FMLearner.__init__).split()),
        "",
        "# Model update rules",
        "",
        "`FMLearner(..., updater=)`, from the entry point "
        "`examples/train.py --model fm --updater {sgd,ftrl}` (`--alpha "
        "--beta --l1 --l2 --v-lr --v-beta`; `--learning-rate` for sgd).",
        "",
        rule_doc(FMLearner),
        "",
        "# Environment knobs",
        "",
        "Every `DMLC_*`/`DCT_*` environment variable the shipped code "
        "reads, extracted from the live tree by `scripts/contracts.py` — "
        "the SAME extraction `make analyze` (Pass 4, "
        "[analysis.md](analysis.md)) diffs this table against, so a knob "
        "added, removed, or re-defaulted without regenerating this page "
        "fails CI. Defaults: a literal is the in-code fallback; `unset` "
        "means the raw value is read with behavior-defined fallback; "
        "`computed` means the default derives from other knobs at run "
        "time; `required` means the process exports it before the read. "
        "Long-form semantics live with each subsystem "
        "([robustness.md](robustness.md), [caching.md](caching.md), "
        "[io-ranged.md](io-ranged.md), [parsing.md](parsing.md), "
        "[observability.md](observability.md), "
        "[benchmarking.md](benchmarking.md)).",
        "",
        contracts.render_knob_table(contracts.collect_repo_knobs(REPO)),
    ])


_LINK_RE = re.compile(r"\]\(([^)#\s]+)(?:#[^)\s]*)?\)")


def check_doc_links() -> None:
    """Cross-reference check: every relative link between doc/*.md pages
    must resolve to an existing file (warnings-as-errors like the rest of
    the lane) — stale links are exactly the doc drift this lane exists to
    stop."""
    for fname in sorted(os.listdir(DOC_DIR)):
        if not fname.endswith(".md"):
            continue
        with open(os.path.join(DOC_DIR, fname), encoding="utf-8") as f:
            text = f.read()
        for i, line in enumerate(text.splitlines(), 1):
            for m in _LINK_RE.finditer(line):
                target = m.group(1)
                if "://" in target or target.startswith("mailto:"):
                    continue
                resolved = os.path.normpath(os.path.join(DOC_DIR, target))
                if not os.path.exists(resolved):
                    warn(f"doc/{fname}:{i}: broken relative link "
                         f"({target})")


def gen_index() -> str:
    return "\n".join([
        "# dmlc_core_tpu documentation",
        "",
        "| page | contents |",
        "|---|---|",
        "| [migration.md](migration.md) | dmlc-core -> dmlc_core_tpu "
        "API mapping |",
        "| [api.md](api.md) | generated Python API reference |",
        "| [parameters.md](parameters.md) | parameter system + native "
        "data-format registry + the generated DMLC_*/DCT_* env-knob "
        "table |",
        "| [parallelism.md](parallelism.md) | the five sharding "
        "strategies (DP/SP/TP/EP/PP) and their oracles |",
        "| [pipeline.md](pipeline.md) | the multi-chunk parse pipeline: "
        "stages, knobs, occupancy counters |",
        "| [parsing.md](parsing.md) | SIMD text ingest: structural "
        "scanner tiers, fused field decoders, DMLC_PARSE_SIMD, the "
        "byte-identical guarantee |",
        "| [caching.md](caching.md) | parse-once/serve-many shard cache: "
        "manifest keying, shard format, mmap zero-copy replay, "
        "never/auto/refresh knobs, failure semantics, elastic "
        "interaction |",
        "| [io-ranged.md](io-ranged.md) | parallel ranged remote reads: "
        "the concurrent range-reader engine, AIMD readahead scheduler "
        "(telemetry-seeded range size + concurrency), per-range retry "
        "isolation, Content-Range verification, 200-degrade to the "
        "sequential lane, DMLC_IO_RANGE* knobs |",
        "| [robustness.md](robustness.md) | remote-I/O resilience (retry "
        "model, env/URI knobs, fault-plan grammar, io_stats()) + "
        "distributed job liveness (heartbeats, dead-rank deadlines, "
        "abort broadcast, state()/event-log schema) |",
        "| [observability.md](observability.md) | the unified telemetry "
        "plane: metric catalog (names/types/units), the three snapshot "
        "surfaces (C ABI / Python / tracker HTTP scrape), Prometheus + "
        "JSONL exposition, env knobs, overhead bounds |",
        "| [analysis.md](analysis.md) | project-native concurrency & "
        "invariant analyzer: the Python lock-discipline pass, "
        "DMLC_GUARDED_BY capability annotations + structural checker, "
        "checked-env-parse / no-assert lints, the cross-boundary "
        "contract passes (C-ABI/ctypes parity + layout probe, metric "
        "catalog, env-knob registry, wire words), the "
        "lock-ok/env-ok/abi-ok/contract-ok escape hatches, the UBSan "
        "lane and the shard-cache fuzz driver |",
        "| [serving.md](serving.md) | batched online scoring: the "
        "admission model (bounded queue, intended-time lateness shed, "
        "circuit breaker), last-good model reloads, draining shutdown, "
        "bucket padding + compile census, endpoint/knob tables |",
        "| [benchmarking.md](benchmarking.md) | how speed is measured: "
        "the cell benchmark (`python3 benchmarks/run.py`, the cells of "
        "`BENCHMARK.json`, configuration / traffic / metric files and "
        "their readers, the result line, exit codes, no CPU fallback, "
        "comparing two commits) and the load rig that stays for cells "
        "to come: out-of-process origins (pre-forked mock backends, one "
        "config surface) and the open-loop generator "
        "(coordinated-omission-safe intended-time capture, shed "
        "policy) |",
        "",
        "Build: `make doc` (part of `make ci`) regenerates api.md and "
        "parameters.md and fails on any undocumented public symbol — the "
        "warnings-as-errors doc lane (reference Makefile:93-97).",
    ])


def main() -> int:
    os.makedirs(DOC_DIR, exist_ok=True)
    pages = {
        "api.md": gen_api(),
        "parameters.md": gen_parameters(),
        "index.md": gen_index(),
    }
    for name, text in pages.items():
        with open(os.path.join(DOC_DIR, name), "w") as f:
            f.write(text.rstrip() + "\n")
        print(f"doc: wrote doc/{name} ({len(text)} bytes)")
    check_doc_links()
    if warnings:
        print(f"doc: {len(warnings)} warning(s) — failing (warnings are "
              f"errors in the doc lane)", file=sys.stderr)
        return 1
    print("doc: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
