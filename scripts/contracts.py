#!/usr/bin/env python3
"""Cross-boundary contract extraction (doc/analysis.md "Pass 4").

Three large hand-maintained contracts span this repo's language boundary:
the C ABI (cpp/src/capi.cc) mirrored by ctypes (dmlc_core_tpu/io/native.py),
the telemetry metric catalog (code registrations vs METRIC_HELP vs
doc/observability.md), and the DMLC_*/DCT_* env-knob registry
(doc/parameters.md). This module is the ONE definition of how each contract
is read out of the sources; both consumers import it:

  - scripts/analyze.py (Pass 4) diffs the extracted halves against each
    other and against the docs — drift is a finding;
  - scripts/gendoc.py renders the env-knob table in doc/parameters.md from
    the same extraction — so the checker and the generator can never
    disagree about what the contract IS.

Everything here is static (regex/AST over text) plus a restricted eval of
ctypes type expressions — importing the bound package (and its numpy/jax
dependency chain) is deliberately avoided so the analyzer runs anywhere,
including on the synthetic fixture trees tests/test_analyze.py drives.
"""

import ast
import ctypes
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# repo-mode scope of the metric + env-knob contracts: the shipped code
# defines them; tests and examples merely configure knobs (analyze.py's
# ContractPass and gendoc.py's table generator both key on this, so the
# checker and the generator see the same sites)
CODE_SCOPE = ("dmlc_core_tpu/", "cpp/src/", "scripts/")

def strip_cpp_comments(text: str) -> str:
    """Blank out comments ONLY (string literals preserved, offsets and
    newlines intact) — the metric/knob extractors match on string
    literals, so analyze.py's full strip (which also blanks strings)
    would erase exactly the names they exist to read."""
    out = list(text)
    i, n = 0, len(text)
    while i < n:
        c = text[i]
        nxt = text[i + 1] if i + 1 < n else ""
        if c == "/" and nxt == "/":
            while i < n and text[i] != "\n":
                out[i] = " "
                i += 1
        elif c == "/" and nxt == "*":
            while i < n and not (text[i] == "*" and i + 1 < n
                                 and text[i + 1] == "/"):
                if text[i] != "\n":
                    out[i] = " "
                i += 1
            if i + 1 < n:
                out[i] = out[i + 1] = " "
            i += 2
        elif c in "\"'":
            quote = c
            i += 1
            while i < n and text[i] != quote:
                i += 2 if text[i] == "\\" else 1
            i += 1
        else:
            i += 1
    return "".join(out)


# ===========================================================================
# C ABI: functions + structs out of capi.cc
# ===========================================================================

# e.g. `int dct_stream_read(dct_stream_t h, void* buf, ...) {`
_CFUNC_RE = re.compile(
    r"(?:^|\n)[ \t]*((?:const[ \t]+)?\w+[ \t]*\**)[ \t\n]*"
    r"(dct_\w+)[ \t]*\(([^)]*)\)[ \t\n]*\{")
_HANDLE_TYPEDEF_RE = re.compile(r"typedef\s+void\s*\*\s*(\w+)\s*;")
_STRUCT_OPEN_RE = re.compile(r"typedef\s+struct\s*\{")
_STRUCT_CLOSE_RE = re.compile(
    r"\}\s*((?:__attribute__\s*\(\([^()]*\)\)\s*)?)(\w+)\s*;")

# exact-width expectations for scalar C types (the 64-bit truncation bug
# class this pass exists for: a uint64_t crossing the boundary as c_int)
SCALAR_CTYPES = {
    "int": "c_int", "unsigned": "c_uint", "unsigned int": "c_uint",
    "int8_t": "c_int8", "uint8_t": "c_uint8",
    "int16_t": "c_int16", "uint16_t": "c_uint16",
    "int32_t": "c_int32", "uint32_t": "c_uint32",
    "int64_t": "c_int64", "uint64_t": "c_uint64",
    "size_t": "c_size_t", "float": "c_float", "double": "c_double",
    "char": "c_char",
}


class CFunc:
    """One extern-"C" ABI function: name, normalized return/param types."""

    def __init__(self, name, ret, params, lineno):
        self.name = name
        self.ret = ret            # normalized C type string, e.g. "char*"
        self.params = params      # [normalized C type string]
        self.lineno = lineno


class CStruct:
    """One ABI struct: fields as (normalized type, name, lineno), plus the
    verbatim declaration text for the compile-time layout probe."""

    def __init__(self, name, fields, text, lineno):
        self.name = name
        self.fields = fields
        self.text = text
        self.lineno = lineno


def _norm_ctype(decl, handles):
    """Normalize one C declarator ("const char* uri") to its bare type
    ("char*"); returns (type, param_name_or_None)."""
    decl = re.sub(r"\bconst\b|\bstruct\b", " ", decl).strip()
    stars = decl.count("*")
    toks = decl.replace("*", " ").split()
    if not toks:
        return "", None
    if len(toks) >= 2 and not (toks[0] == "unsigned" and len(toks) == 2
                               and toks[1] in ("int", "long", "char")):
        base, name = " ".join(toks[:-1]), toks[-1]
    elif toks[:1] == ["unsigned"] and toks[1:2] == ["int"]:
        base, name = "unsigned", None
    else:
        base, name = " ".join(toks), None
    if base == "unsigned int":
        base = "unsigned"
    if base in handles:          # typedef void* dct_stream_t
        return "void*" + "*" * stars, name
    return base + "*" * stars, name


def parse_c_abi(text, stripped):
    """Extract (funcs, structs, handles) from a capi-style source. `text`
    is the raw file, `stripped` the comment/string-blanked twin (same
    offsets — scripts/analyze.py strip_cpp)."""
    handles = set(_HANDLE_TYPEDEF_RE.findall(stripped))
    structs = {}
    for m in _STRUCT_OPEN_RE.finditer(stripped):
        close = _STRUCT_CLOSE_RE.search(stripped, m.end())
        if close is None:
            continue
        name = close.group(2)
        body = stripped[m.end():close.start()]
        base_line = stripped.count("\n", 0, m.start()) + 1
        fields = []
        for off, decl in _iter_semis(body):
            ftype, fname = _norm_ctype(decl, handles)
            if fname is None:
                continue
            fields.append((ftype, fname,
                           base_line + body.count("\n", 0, off)))
        structs[name] = CStruct(name, fields,
                                text[m.start():close.end()], base_line)
    funcs = {}
    for m in _CFUNC_RE.finditer(stripped):
        ret, _ = _norm_ctype(m.group(1) + " x", handles)
        name = m.group(2)
        params = []
        ptext = m.group(3).strip()
        if ptext and ptext != "void":
            for p in ptext.split(","):
                ptype, _pname = _norm_ctype(p, handles)
                if ptype:
                    params.append(ptype)
        funcs[name] = CFunc(name, ret,
                            params, stripped.count("\n", 0, m.start()) + 1)
    return funcs, structs, handles


def _iter_semis(body):
    """(offset, declaration) per ';'-terminated declaration in a struct
    body."""
    start = 0
    while True:
        semi = body.find(";", start)
        if semi < 0:
            return
        yield start, body[start:semi]
        start = semi + 1


# ===========================================================================
# ctypes side: the signature table and the Structure mirrors
# ===========================================================================

class PyBinding:
    """One ctypes binding row: restype is None when the table still uses
    the legacy argtypes-only list form (implicit c_int restype)."""

    def __init__(self, name, restype, argtypes, lineno):
        self.name = name
        self.restype = restype    # canonical string or None (legacy form)
        self.argtypes = argtypes  # [canonical string]
        self.lineno = lineno


class PyMirror:
    """One ctypes.Structure mirror: maps to C struct `cname` via its
    'Mirror of <cname>' docstring convention."""

    def __init__(self, pyname, cname, fields, lineno):
        self.pyname = pyname
        self.cname = cname
        self.fields = fields      # [(name, canonical type string, lineno)]
        self.lineno = lineno


def _ctype_canon(node, aliases):
    """Canonicalize a ctypes type expression AST node: `c.c_int` ->
    "c_int", `vp` -> resolved alias, `c.POINTER(X)` -> "POINTER(<X>)",
    bare class names stay (struct mirrors). None when unrecognizable."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return aliases.get(node.id, node.id)
    if isinstance(node, ast.Call):
        fn = node.func
        fname = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if fname == "POINTER" and node.args:
            inner = _ctype_canon(node.args[0], aliases)
            return f"POINTER({inner})" if inner else None
    return None


def _alias_map(func_node):
    """Local single-letter ctypes aliases in a declaration function
    (`vp, sz, i, u = c.c_void_p, ...` and `c = ctypes`)."""
    aliases = {}
    for st in ast.walk(func_node):
        if not isinstance(st, ast.Assign):
            continue
        tgts, vals = st.targets, None
        if len(tgts) == 1 and isinstance(tgts[0], ast.Tuple) and \
                isinstance(st.value, ast.Tuple):
            pairs = zip(tgts[0].elts, st.value.elts)
        elif len(tgts) == 1 and isinstance(tgts[0], ast.Name):
            pairs = [(tgts[0], st.value)]
        else:
            continue
        for t, v in pairs:
            if not isinstance(t, ast.Name):
                continue
            if isinstance(v, ast.Attribute):
                aliases[t.id] = v.attr
            elif isinstance(v, ast.Name) and v.id == "ctypes":
                aliases[t.id] = "ctypes"
        del vals
    return aliases


def extract_bindings(tree):
    """Find the dct_* signature table (the dict literal whose keys are
    dct_* strings) and return {name: PyBinding}. Supports both the
    explicit `name: (restype, [argtypes])` form and the legacy
    `name: [argtypes]` list form (restype None)."""
    best = None
    for st in ast.walk(tree):
        if not (isinstance(st, ast.Assign)
                and isinstance(st.value, ast.Dict)):
            continue
        keys = [k.value for k in st.value.keys
                if isinstance(k, ast.Constant)
                and isinstance(k.value, str)]
        dct = [k for k in keys if k.startswith("dct_")]
        if dct and (best is None or len(dct) > len(best[0])):
            best = (dct, st.value)
    if best is None:
        return {}
    aliases = _alias_map(tree)
    out = {}
    for k, v in zip(best[1].keys, best[1].values):
        if not (isinstance(k, ast.Constant) and isinstance(k.value, str)
                and k.value.startswith("dct_")):
            continue
        restype, arglist = None, None
        if isinstance(v, (ast.Tuple, ast.List)) and len(v.elts) == 2 and \
                isinstance(v.elts[1], ast.List):
            restype = _ctype_canon(v.elts[0], aliases)
            arglist = v.elts[1]
        elif isinstance(v, ast.List):
            arglist = v
        argtypes = []
        if arglist is not None:
            for el in arglist.elts:
                argtypes.append(_ctype_canon(el, aliases) or "<unknown>")
        out[k.value] = PyBinding(k.value, restype, argtypes, k.lineno)
    return out


_MIRROR_DOC_RE = re.compile(r"Mirror of (\w+)")


def extract_mirrors(tree):
    """ctypes.Structure subclasses carrying the 'Mirror of <cstruct>'
    docstring convention -> {cname: PyMirror}."""
    out = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        if not any((isinstance(b, ast.Attribute) and b.attr == "Structure")
                   or (isinstance(b, ast.Name) and b.id == "Structure")
                   for b in node.bases):
            continue
        doc = ast.get_docstring(node) or ""
        m = _MIRROR_DOC_RE.search(doc)
        if not m:
            continue
        fields = []
        for st in node.body:
            if isinstance(st, ast.Assign) and len(st.targets) == 1 and \
                    isinstance(st.targets[0], ast.Name) and \
                    st.targets[0].id == "_fields_" and \
                    isinstance(st.value, (ast.List, ast.Tuple)):
                for el in st.value.elts:
                    if isinstance(el, ast.Tuple) and len(el.elts) == 2 and \
                            isinstance(el.elts[0], ast.Constant):
                        fields.append((el.elts[0].value,
                                       _ctype_canon(el.elts[1], {})
                                       or "<unknown>", el.lineno))
        out[m.group(1)] = PyMirror(node.name, m.group(1), fields,
                                   node.lineno)
    return out


def expected_restype(c_ret):
    """Canonical ctypes restype for a normalized C return type."""
    if c_ret == "char*":
        return "c_char_p"
    return SCALAR_CTYPES.get(c_ret)


def ctype_mismatch(c_type, py_canon, mirrors):
    """Why `py_canon` cannot carry C type `c_type` across the boundary,
    or None when compatible. Pointer params accept c_void_p (the numpy
    data-pointer lane, nullable) or an exactly-typed POINTER; scalars
    must be exact-width."""
    if c_type in SCALAR_CTYPES:
        want = SCALAR_CTYPES[c_type]
        # c_int carries int; but a same-width alias is equally safe
        same = {"c_int": {"c_int", "c_int32"}, "c_int32": {"c_int32"},
                "c_uint": {"c_uint", "c_uint32"}}
        if py_canon in same.get(want, {want}):
            return None
        return f"C `{c_type}` needs {want}, binding declares {py_canon}"
    if not c_type.endswith("*"):
        return f"unhandled C type `{c_type}`"
    pointee = c_type[:-1]
    if py_canon == "c_void_p":
        return None
    if pointee in ("char", "void") and py_canon == "c_char_p":
        return None
    m = re.fullmatch(r"POINTER\((\w+)\)", py_canon or "")
    if m:
        inner = m.group(1)
        if pointee == "void*" and inner == "c_void_p":
            return None
        if pointee == "char*" and inner == "c_char_p":
            return None
        if pointee in SCALAR_CTYPES and inner == SCALAR_CTYPES[pointee]:
            return None
        if pointee in mirrors and inner == mirrors[pointee].pyname:
            return None
    return (f"C `{c_type}` needs c_void_p or a matching POINTER, "
            f"binding declares {py_canon}")


# ===========================================================================
# compile-time layout probe
# ===========================================================================

def _layout_ctype(canon):
    """A ctypes object layout-equivalent to the canonical string (every
    pointer has one layout, so POINTER(...)/c_char_p map to c_void_p)."""
    if canon.startswith("POINTER(") or canon in ("c_char_p", "c_void_p"):
        return ctypes.c_void_p
    return getattr(ctypes, canon, None)


def build_mirror_class(mirror):
    """Materialize a PyMirror as a real ctypes.Structure for
    sizeof/offset comparison; None when a field type is unknown."""
    fields = []
    for fname, canon, _ln in mirror.fields:
        obj = _layout_ctype(canon)
        if obj is None:
            return None
        fields.append((fname, obj))
    return type(mirror.pyname, (ctypes.Structure,), {"_fields_": fields})


def find_cxx():
    """The first available C++-capable compiler, or None."""
    for cc in ("g++", "c++", "clang++", "gcc", "cc"):
        path = shutil.which(cc)
        if path:
            return path
    return None


def emit_probe_source(structs):
    """A standalone C++ program printing sizeof/offsetof for every ABI
    struct as one JSON document (the structs are emitted VERBATIM, so the
    probe compiles exactly the member declarations the .so compiles)."""
    lines = ["#include <cstddef>", "#include <cstdint>",
             "#include <cstdio>", ""]
    for s in structs.values():
        lines.append(s.text)
        lines.append("")
    lines.append("int main() {")
    lines.append('  printf("{");')
    for i, s in enumerate(structs.values()):
        sep = ", " if i else ""
        lines.append(
            f'  printf("{sep}\\"{s.name}\\": {{\\"size\\": %zu, '
            f'\\"fields\\": {{", sizeof({s.name}));')
        for j, (_t, fname, _ln) in enumerate(s.fields):
            fsep = ", " if j else ""
            lines.append(
                f'  printf("{fsep}\\"{fname}\\": %zu", '
                f'offsetof({s.name}, {fname}));')
        lines.append('  printf("}}");')
    lines.append('  printf("}\\n");')
    lines.append("  return 0;")
    lines.append("}")
    return "\n".join(lines) + "\n"


def run_layout_probe(structs):
    """Compile + run the layout probe. Returns (layout_dict, note):
    layout_dict is {struct: {"size": n, "fields": {name: offset}}} or
    None when no compiler is present / the probe failed, with `note`
    explaining why (the loud-skip contract)."""
    if not structs:
        return {}, None
    cxx = find_cxx()
    if cxx is None:
        return None, ("no C/C++ compiler on PATH — layout probe SKIPPED "
                      "(struct sizes/offsets NOT proven this run)")
    src = emit_probe_source(structs)
    with tempfile.TemporaryDirectory(prefix="abi_probe_") as tmp:
        cc_path = os.path.join(tmp, "probe.cc")
        bin_path = os.path.join(tmp, "probe")
        with open(cc_path, "w") as f:
            f.write(src)
        comp = subprocess.run([cxx, "-o", bin_path, cc_path],
                              capture_output=True, text=True)
        if comp.returncode != 0:
            return None, (f"layout probe failed to compile under {cxx} "
                          f"(SKIPPED): {comp.stderr.strip()[:300]}")
        run = subprocess.run([bin_path], capture_output=True, text=True)
        if run.returncode != 0:
            return None, "layout probe binary failed to run (SKIPPED)"
        try:
            return json.loads(run.stdout), None
        except ValueError:
            return None, "layout probe emitted unparsable output (SKIPPED)"


# ===========================================================================
# metric contract: code registrations, METRIC_HELP, the doc catalog
# ===========================================================================

class MetricReg:
    """Everything observed about one metric name across both halves."""

    def __init__(self):
        self.kinds = set()        # {"counter","gauge","histogram"}
        self.halves = set()       # {"cpp","py"}
        self.labels = {}          # half -> set of frozenset(label keys)
        self.sites = []           # [(rel, lineno)]

    def add(self, half, kind, keys, rel, lineno):
        self.kinds.add(kind)
        self.halves.add(half)
        if keys is not None:
            self.labels.setdefault(half, set()).add(frozenset(keys))
        self.sites.append((rel, lineno))


_CPP_METRIC_RE = re.compile(
    r"\b(GetCounter|GetGauge|GetHist|RegisterExternalCounter)"
    r"\s*\(\s*\"([\w:]+)\"")
_CPP_KINDS = {"GetCounter": "counter", "GetGauge": "gauge",
              "GetHist": "histogram", "RegisterExternalCounter": "counter"}
_PY_KINDS = {"counter": "counter", "gauge": "gauge",
             "histogram": "histogram"}


def _cpp_labels_at(stripped, pos):
    """Label keys of the registration call starting after `pos` (the end
    of the name literal): an inline `{{"k", v}}` initializer, a nearby
    `labels{{...}}` variable, or None (unknown -> no label check)."""
    stmt_end = stripped.find(";", pos)
    seg = stripped[pos:stmt_end if stmt_end >= 0 else pos + 200]
    if "{{" in seg:
        return set(re.findall(r'\{\s*"(\w+)"\s*,', seg))
    m = re.search(r",\s*(\w+)\s*\)", seg)
    if not m:
        return set()              # no second argument: unlabeled
    ident = m.group(1)
    init = None
    for im in re.finditer(rf"\b{re.escape(ident)}\s*(?:=\s*)?\{{\{{",
                          stripped[:pos]):
        init = im
    if init is None:
        return None
    end = stripped.find("};", init.end())
    return set(re.findall(r'\{\s*"(\w+)"\s*,',
                          stripped[init.start():end if end >= 0 else
                                   init.start() + 300]))


def extract_metrics_cpp(rel, stripped, registry):
    """Collect telemetry registrations out of one stripped C++ file."""
    for m in _CPP_METRIC_RE.finditer(stripped):
        kind = _CPP_KINDS[m.group(1)]
        name = m.group(2)
        line = stripped.count("\n", 0, m.start()) + 1
        keys = _cpp_labels_at(stripped, m.end())
        registry.setdefault(name, MetricReg()).add(
            "cpp", kind, keys, rel, line)


def _dict_const_keys(node):
    """Constant string keys of a Dict literal, or None when any key is
    dynamic (labels unknown)."""
    keys = set()
    for k in node.keys:
        if isinstance(k, ast.Constant) and isinstance(k.value, str):
            keys.add(k.value)
        else:
            return None
    return keys


def extract_metrics_py(rel, tree, registry):
    """Collect telemetry registrations out of one Python module: calls to
    telemetry.counter/gauge/histogram (bare names too inside the registry
    module itself), plus the synthesized-series pattern the snapshot uses
    (`doc["gauges"].append({"name": <literal>, ...})`)."""
    is_registry_module = any(
        isinstance(n, ast.FunctionDef) and n.name == "counter"
        for n in tree.body)
    # ident -> [(lineno, keys)] for literal dict assigns (labels vars)
    dict_assigns = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                isinstance(node.value, ast.Dict):
            keys = _dict_const_keys(node.value)
            if keys is not None:
                dict_assigns.setdefault(node.targets[0].id, []).append(
                    (node.lineno, keys))

    def labels_of(node, lineno):
        if node is None:
            return set()
        if isinstance(node, ast.Dict):
            return _dict_const_keys(node)
        if isinstance(node, ast.Constant) and node.value is None:
            return set()
        if isinstance(node, ast.Name):
            prior = [ks for ln, ks in dict_assigns.get(node.id, ())
                     if ln <= lineno]
            return prior[-1] if prior else None
        return None

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        kind = None
        if isinstance(fn, ast.Attribute) and fn.attr in _PY_KINDS and \
                isinstance(fn.value, ast.Name) and \
                fn.value.id == "telemetry":
            kind = _PY_KINDS[fn.attr]
        elif is_registry_module and isinstance(fn, ast.Name) and \
                fn.id in _PY_KINDS:
            kind = _PY_KINDS[fn.id]
        if kind is not None:
            if not (node.args and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            name = node.args[0].value
            labels_node = node.args[1] if len(node.args) > 1 else None
            for kw in node.keywords:
                if kw.arg == "labels":
                    labels_node = kw.value
            registry.setdefault(name, MetricReg()).add(
                "py", kind, labels_of(labels_node, node.lineno), rel,
                node.lineno)
            continue
        # synthesized series: doc["gauges"].append({"name": "...", ...})
        if isinstance(fn, ast.Attribute) and fn.attr == "append" and \
                isinstance(fn.value, ast.Subscript) and \
                node.args and isinstance(node.args[0], ast.Dict):
            sub = fn.value.slice
            fam = sub.value if isinstance(sub, ast.Constant) else None
            if fam not in ("counters", "gauges", "histograms"):
                continue
            d = node.args[0]
            name, keys = None, set()
            for k, v in zip(d.keys, d.values):
                if isinstance(k, ast.Constant) and k.value == "name" and \
                        isinstance(v, ast.Constant) and \
                        isinstance(v.value, str):
                    name = v.value
                if isinstance(k, ast.Constant) and k.value == "labels":
                    keys = (_dict_const_keys(v)
                            if isinstance(v, ast.Dict) else None)
            if name is not None:
                registry.setdefault(name, MetricReg()).add(
                    "py", fam[:-1] if fam != "histograms" else "histogram",
                    keys, rel, node.lineno)


def extract_metric_help(tree):
    """{metric name: lineno} of the METRIC_HELP catalog dict, or None
    when the module defines none."""
    for node in ast.walk(tree):
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target = node.targets[0]
        elif isinstance(node, ast.AnnAssign):
            target = node.target
        if not (isinstance(target, ast.Name)
                and target.id == "METRIC_HELP"):
            continue
        value = node.value
        if not isinstance(value, ast.Dict):
            continue
        return {k.value: k.lineno for k in value.keys
                if isinstance(k, ast.Constant) and isinstance(k.value, str)}
    return None


# `name`, `name{op=}`, or the multi-key form `name{op=,fs=}`
_DOC_METRIC_TOKEN_RE = re.compile(
    r"`([a-z][a-z0-9_]*)(\{(\w+=(?:,\w+=)*)\})?`")


def extract_doc_catalog(md_text):
    """Metric rows out of every `| metric | type | ... |` table in a doc
    page -> {name: {"labels": set, "kind": str|None, "line": int}}."""
    out = {}
    in_table = False
    for i, line in enumerate(md_text.splitlines(), 1):
        s = line.strip()
        if not s.startswith("|"):
            in_table = False
            continue
        cells = [c.strip() for c in s.strip("|").split("|")]
        if cells and cells[0].lower() == "metric":
            in_table = True
            continue
        if not in_table or not cells or set(cells[0]) <= {"-", " "}:
            continue
        kind = None
        if len(cells) > 1:
            kw = cells[1].split()
            if kw and kw[0] in ("counter", "gauge", "histogram"):
                kind = kw[0]
        for m in _DOC_METRIC_TOKEN_RE.finditer(cells[0]):
            name = m.group(1)
            labels = ({k.rstrip("=") for k in m.group(3).split(",")}
                      if m.group(3) else set())
            if name not in out:
                out[name] = {"labels": labels, "kind": kind, "line": i}
    return out


# ===========================================================================
# env-knob registry: every DMLC_*/DCT_* read, with its default
# ===========================================================================

_KNOB_NAME_RE = re.compile(r"^(?:DMLC|DCT)_[A-Z0-9_]+$")
_PY_ENV_HELPERS = {"env_int", "env_float", "env_enum", "env_int_opt",
                   "env_str"}


class KnobSite:
    """One read of an env knob: where, and with what default. `default`
    is the canonical display string, "computed" for non-literal defaults
    (wildcard in the drift check), "unset"/"required" for default-less
    reads."""

    def __init__(self, rel, lineno, default):
        self.rel = rel
        self.lineno = lineno
        self.default = default


def _canon_default(value):
    """Display form of a literal default (None -> "unset"; int-valued
    floats collapse so env_int(…, 5) and env_float(…, 5.0) agree)."""
    if value is None or value == "":
        return "unset"
    if isinstance(value, bool):
        return str(int(value))
    if isinstance(value, float) and value.is_integer():
        return str(int(value))
    return str(value)


def extract_knobs_py(rel, tree, registry):
    """Collect DMLC_*/DCT_* env reads out of one Python module: the
    checked wire.env_* helpers, os.environ.get/os.getenv, and required
    `os.environ["X"]` subscript reads."""
    def dotted(node):
        parts = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts))
        return None

    def record(name, lineno, default):
        if _KNOB_NAME_RE.match(name):
            registry.setdefault(name, []).append(
                KnobSite(rel, lineno, default))

    def const_default(node):
        if node is None:
            return "unset"
        if isinstance(node, ast.Constant):
            return _canon_default(node.value)
        if isinstance(node, ast.UnaryOp) and \
                isinstance(node.op, ast.USub) and \
                isinstance(node.operand, ast.Constant):
            return _canon_default(-node.operand.value)
        return "computed"

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            tail = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            d = dotted(fn)
            if tail in _PY_ENV_HELPERS and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                if tail == "env_int_opt":
                    default = "unset"
                else:
                    darg = node.args[1] if len(node.args) > 1 else None
                    for kw in node.keywords:
                        if kw.arg == "default":
                            darg = kw.value
                    default = const_default(darg)
                record(node.args[0].value, node.lineno, default)
            elif d in ("os.environ.get", "os.getenv") and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                darg = node.args[1] if len(node.args) > 1 else None
                record(node.args[0].value, node.lineno,
                       const_default(darg))
        elif isinstance(node, ast.Subscript) and \
                isinstance(node.ctx, ast.Load) and \
                dotted(node.value) == "os.environ" and \
                isinstance(node.slice, ast.Constant) and \
                isinstance(node.slice.value, str):
            record(node.slice.value, node.lineno, "required")


_CPP_CHECKED_ENV_RE = re.compile(
    r"\bCheckedEnvInt\(\s*\"((?:DMLC|DCT)_[A-Z0-9_]+)\"\s*,\s*([^,]+),")
_CPP_ENVOVERRIDE_RE = re.compile(
    r"\bEnvOverride\(\s*\"((?:DMLC|DCT)_[A-Z0-9_]+)\"")
_CPP_GETENV_RE = re.compile(
    r"\bgetenv\(\s*\"((?:DMLC|DCT)_[A-Z0-9_]+)\"\s*\)")
_CPP_NUM_RE = re.compile(r"^-?\d+(?:LL|L|UL|ULL|U)?$")


def extract_knobs_cpp(rel, stripped, registry):
    """Collect DMLC_*/DCT_* env reads out of one stripped C++ file."""
    def record(name, pos, default):
        registry.setdefault(name, []).append(
            KnobSite(rel, stripped.count("\n", 0, pos) + 1, default))

    for m in _CPP_CHECKED_ENV_RE.finditer(stripped):
        tok = m.group(2).strip()
        default = (_canon_default(int(re.sub(r"[A-Z]+$", "", tok)))
                   if _CPP_NUM_RE.match(tok) else "computed")
        record(m.group(1), m.start(), default)
    for m in _CPP_ENVOVERRIDE_RE.finditer(stripped):
        record(m.group(1), m.start(), "computed")
    for m in _CPP_GETENV_RE.finditer(stripped):
        record(m.group(1), m.start(), "unset")


def knob_display_default(sites):
    """The default the doc table shows for one knob: the (post-drift-fix
    unique) literal when any site carries one, else "computed"/"unset"."""
    literals = sorted({s.default for s in sites
                       if s.default not in ("computed", "unset",
                                            "required")})
    if literals:
        return literals[0]
    if any(s.default == "computed" for s in sites):
        return "computed"
    if all(s.default == "required" for s in sites):
        return "required"
    return "unset"


def knob_conflicts(sites):
    """Distinct literal defaults for one knob (len > 1 = drift)."""
    return sorted({s.default for s in sites
                   if s.default not in ("computed", "unset", "required")})


def collect_repo_knobs(root):
    """Walk the repo's contract scope (CODE_SCOPE) and return the full
    env-knob registry {name: [KnobSite]} — the one extraction both
    `make doc` (table generation) and `make analyze` (drift check) use."""
    from srcwalk import iter_sources
    registry = {}
    for path in iter_sources(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        if not any(rel.startswith(p) for p in CODE_SCOPE):
            continue
        with open(path, encoding="utf-8", errors="replace") as fh:
            text = fh.read()
        if path.endswith(".py"):
            try:
                tree = ast.parse(text, filename=rel)
            except SyntaxError:
                continue
            extract_knobs_py(rel, tree, registry)
        elif rel.startswith("cpp/src/"):
            # C++ scope must mirror analyze.py exactly: its driver only
            # loads C++ from the cpp/ tree, so a .cc elsewhere in
            # CODE_SCOPE (e.g. scripts/) must not feed the generator
            # either — a row only `make doc` can see would deadlock the
            # two lanes (each telling the operator to run the other)
            extract_knobs_cpp(rel, strip_cpp_comments(text), registry)
    return registry


KNOB_TABLE_BEGIN = "<!-- BEGIN GENERATED: env-knobs (scripts/contracts.py)"
KNOB_TABLE_END = "<!-- END GENERATED: env-knobs -->"


def render_knob_table(registry):
    """The generated env-knob table (between the markers analyze.py keys
    on). Defaults: `unset` = read raw with in-code fallback behavior,
    `required` = the process exports it before the read, `computed` =
    derived from other knobs at run time."""
    lines = [KNOB_TABLE_BEGIN + " — edit code, not this table -->", "",
             "| knob | default | referenced in |", "|---|---|---|"]
    for name in sorted(registry):
        sites = registry[name]
        files = sorted({s.rel for s in sites})
        shown = ", ".join(f"`{f}`" for f in files[:3])
        if len(files) > 3:
            shown += f" +{len(files) - 3} more"
        lines.append(f"| `{name}` | `{knob_display_default(sites)}` "
                     f"| {shown} |")
    lines += ["", KNOB_TABLE_END]
    return "\n".join(lines)


def parse_knob_table(md_text):
    """(rows, found): {knob: default} parsed from the generated block in
    a doc page; found=False when the markers are absent."""
    begin = md_text.find(KNOB_TABLE_BEGIN)
    end = md_text.find(KNOB_TABLE_END)
    if begin < 0 or end < 0:
        return {}, False
    rows = {}
    for line in md_text[begin:end].splitlines():
        m = re.match(r"\|\s*`((?:DMLC|DCT)_[A-Z0-9_]+)`\s*\|\s*`([^`]*)`",
                     line.strip())
        if m:
            rows[m.group(1)] = m.group(2)
    return rows, True


# ===========================================================================
# wire-protocol words (tracker/wire.py)
# ===========================================================================

class WireWords:
    """The channel word registry of one wire module: every module-level
    int constant, plus the declared command/sentinel registries."""

    def __init__(self):
        self.constants = {}       # name -> (value, lineno)
        self.commands = {}        # name -> (value_or_None, lineno)
        self.sentinels = {}
        self.has_registry = False


def _int_const(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        inner = _int_const(node.operand)
        return -inner if inner is not None else None
    return None


def extract_wire_words(tree):
    """Parse a wire module: module-level `NAME = <int>` constants and the
    CHANNEL_COMMAND_WORDS / CHANNEL_SENTINELS registry dicts (values may
    be Name references to the constants or int literals)."""
    ww = WireWords()
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            continue
        tname = node.targets[0].id
        iv = _int_const(node.value)
        if iv is not None and tname.isupper():
            ww.constants[tname] = (iv, node.lineno)
            continue
        if tname in ("CHANNEL_COMMAND_WORDS", "CHANNEL_SENTINELS") and \
                isinstance(node.value, ast.Dict):
            ww.has_registry = True
            dest = (ww.commands if tname == "CHANNEL_COMMAND_WORDS"
                    else ww.sentinels)
            for k, v in zip(node.value.keys, node.value.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(k.value, str)):
                    continue
                if isinstance(v, ast.Name):
                    dest[k.value] = (v.id, k.lineno)
                else:
                    dest[k.value] = (_int_const(v), k.lineno)
    return ww
