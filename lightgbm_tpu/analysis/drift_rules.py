"""Layer 3b: cross-artifact drift censuses (D-rules).

The repo's gated inventories — the telemetry name families, perf_gate's
key sets, the CLI knob surface — were each maintained BY HAND next to
the code that feeds them, and PRs 12-14 all shipped drift: counters
documented but never emitted, emitted keys (``predict_b32_*``) that no
gate read, knobs documented only in prose.  These rules run the
censuses from the graftlint driver so drift fails the pre-merge gate:

- **D1 telemetry-inventory** — the counter/route/span/wire-site names
  the package source actually emits (``telemetry.count``/
  ``count_route``/``span``/``collective_span``/``record_collective``
  string literals, plus telemetry.py's internal ``_counters[...]``
  writes) vs the machine-readable family inventory in ``telemetry.py``
  (``COUNTER_FAMILIES``/``SPAN_FAMILIES``/``WIRE_SITE_FAMILIES``).
  Undocumented usage AND stale documentation are both findings; names
  with runtime-computed suffixes census as ``prefix*`` patterns, and
  fully-dynamic wire sites (variable labels built by the learners) live
  in ``DYNAMIC_WIRE_SITES``, documented but exempt from the stale
  check the static census cannot decide.
- **D2 perf-gate-coverage** — every key in perf_gate's ``RATE_KEYS``/
  ``LATENCY_KEYS``/``ABSOLUTE_ZERO_KEYS``/``ABSOLUTE_TRUE_KEYS`` must
  be emitted by ``bench.py``/``__graft_entry__.py`` or present in a
  recorded ``BENCH_r*``/``MULTICHIP_r*`` round (a stale gate key
  silently gates nothing); and, the other direction, every bench.py
  emission whose name SHAPE marks it gateable (``*_per_sec`` rates,
  ``*_spread`` noise bands, ``*_p99_us`` tails, ``*_recompiles``/
  ``*_misscored`` zero contracts, ``*_restore_exact`` truth contracts)
  must be wired into the matching gate set or carried on the
  documented informational allowlist below.
- **D3 config-knob-inventory** — every parameter a ``*Config.set``
  reads must have an entry in cli.py's machine-readable
  ``KNOB_INVENTORY`` and a reject/fatal path (a typed loud getter, a
  ``log.check``/``log.fatal`` in its parse block, or an explicit
  allowlist justification for free-form/externally-validated values);
  and every dataclass field must be reachable from ``set`` or on the
  internal-field allowlist — a field nobody can set, or a knob nobody
  documented, is drift.

All three operate on SOURCE TEXT handed in by the driver (plus the
stdlib-importable telemetry/hatches inventories), so the layer runs
without JAX like layers 1 and 3a, and tests can feed synthetic
artifact sets to prove each census live.
"""
from __future__ import annotations

import ast
import json
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .ast_rules import _annotate_parents, _attr_chain, _terminal_name
from .findings import Finding

# ----------------------------------------------------------------- D1

# telemetry-name emitting calls: api kind -> (terminal call name, arg
# index of the NAME)
_TELEMETRY_CALLS = {
    "count": ("counter", 0),
    "count_route": ("counter", 1),     # arg 0 is the route group
    "gauge": ("counter", 0),
    "span": ("span", 0),
    "collective_span": ("wire", 0),
    "record_collective": ("wire", 0),
}


def _names_of(arg: ast.AST) -> List[Tuple[str, bool]]:
    """The ``(name, is_prefix)`` resolutions of a telemetry-name
    argument: a plain string constant, both arms of an either/or
    (``"a" if cond else "b"``), the constant head of a ``"x/" + suffix``
    concatenation or an f-string.  Empty when fully dynamic."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return [(arg.value, False)]
    if isinstance(arg, ast.IfExp):
        return _names_of(arg.body) + _names_of(arg.orelse)
    if (isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add)
            and isinstance(arg.left, ast.Constant)
            and isinstance(arg.left.value, str)):
        return [(arg.left.value, True)]
    if (isinstance(arg, ast.JoinedStr) and arg.values
            and isinstance(arg.values[0], ast.Constant)
            and isinstance(arg.values[0].value, str)):
        return [(arg.values[0].value, True)]
    return []


def collect_telemetry_usage(files: Dict[str, str]
                            ) -> Dict[Tuple[str, str, bool],
                                      List[Tuple[str, int]]]:
    """Census the package source for telemetry name emissions.

    Returns ``{(kind, name, is_prefix): [(path, line), ...]}`` where
    ``kind`` is counter/span/wire.  ``telemetry.py``'s own internal
    ``_counters[<const>]`` writes census as counters (the compile
    listener's jit/* keys have no public call site)."""
    usage: Dict[Tuple[str, str, bool], List[Tuple[str, int]]] = {}

    def add(kind: str, name: str, prefix: bool, path: str, line: int):
        usage.setdefault((kind, name, prefix), []).append((path, line))

    for path in sorted(files):
        tree = ast.parse(files[path], filename=path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                spec = _TELEMETRY_CALLS.get(_terminal_name(node.func))
                if spec is None:
                    continue
                # the receiver must be the telemetry module (or its _tl
                # alias) — a bare str.count()/dict.get() must not census
                chain = _attr_chain(node.func)
                if len(chain) < 2 or not ("telemetry" in chain[-2]
                                          or chain[-2] == "_tl"):
                    continue
                kind, idx = spec
                if len(node.args) <= idx:
                    continue
                for name, is_prefix in _names_of(node.args[idx]):
                    add(kind, name, is_prefix, path, node.lineno)
            elif (isinstance(node, ast.Subscript)
                    and _attr_chain(node.value) == ["_counters"]
                    and path.endswith("telemetry.py")):
                for name, is_prefix in _names_of(node.slice):
                    add("counter", name, is_prefix, path, node.lineno)
    return usage


def _matches(name: str, is_prefix: bool, entries: Iterable[str]) -> bool:
    """Does a censused name fall under any inventory entry?  Entries
    ending in ``*`` are prefix families."""
    for entry in entries:
        if entry.endswith("*"):
            head = entry[:-1]
            if name.startswith(head) or (is_prefix
                                         and head.startswith(name)):
                return True
        elif not is_prefix and name == entry:
            return True
        elif is_prefix and entry.startswith(name):
            return True
    return False


def check_telemetry_inventory(files: Dict[str, str],
                              inventories: Optional[dict] = None,
                              telemetry_path: str =
                              "lightgbm_tpu/telemetry.py"
                              ) -> List[Finding]:
    """D1: code census vs the documented families, both directions."""
    if inventories is None:
        from .. import telemetry
        inventories = {
            "counter": telemetry.COUNTER_FAMILIES,
            "span": telemetry.SPAN_FAMILIES,
            "wire": telemetry.WIRE_SITE_FAMILIES,
            "dynamic": telemetry.DYNAMIC_WIRE_SITES,
        }
    usage = collect_telemetry_usage(files)
    findings: List[Finding] = []
    for (kind, name, is_prefix), sites in sorted(usage.items()):
        entries = tuple(inventories.get(kind, ())) + tuple(
            inventories.get("dynamic", ()) if kind == "wire" else ())
        if not _matches(name, is_prefix, entries):
            path, line = sites[0]
            findings.append(Finding(
                "D1", path, line, kind,
                name + ("*" if is_prefix else ""),
                "telemetry %s name emitted by code but missing from the "
                "documented %s family inventory (telemetry.py) — the "
                "one-source-of-truth doc has drifted" % (kind, kind)))
    # stale documentation: a documented STATIC family entry no code emits
    tel_src = files.get(telemetry_path, "")
    for kind in ("counter", "span", "wire"):
        used = [(n, p) for (k, n, p) in usage if k == kind]
        for entry in inventories.get(kind, ()):
            if entry.endswith("*"):
                head = entry[:-1]
                live = any(n.startswith(head) or n == head.rstrip("/")
                           for n, _p in used)
            else:
                live = any((not p and n == entry)
                           or (p and entry.startswith(n))
                           for n, p in used)
            if not live:
                findings.append(Finding(
                    "D1", telemetry_path,
                    _line_of(tel_src, entry), kind, entry,
                    "documented telemetry %s family entry that no code "
                    "emits — stale documentation gates nothing" % kind))
    return findings


def _line_of(src: str, needle: str) -> int:
    for i, line in enumerate(src.splitlines(), 1):
        if '"%s"' % needle in line or "'%s'" % needle in line:
            return i
    return 0


# ----------------------------------------------------------------- D2

# bench.py emissions that LOOK gateable but are deliberately
# informational — each with the written reason (the D-rule analogue of
# the baseline's justification strings; graftlint reports any entry
# here that stops matching an emission as stale)
D2_INFORMATIONAL = {
    "cuda_anchor_iters_per_sec":
        "the CUDA anchor is the comparison DENOMINATOR, not a lane of "
        "ours — vs_cuda gates the ratio",
    "ingest_sync_rows_per_sec":
        "depth-0 A/B reference of the gated ingest_rows_per_sec lane",
    "ingest_serial_rows_per_sec":
        "same-record serial reference lane of the parallel-parse "
        "must-GROW check (ISSUE 18) — perf_gate consumes it as the "
        "workers-lane baseline, not as its own trend series",
    "predict_scan_b65536_rows_per_sec":
        "legacy per-tree-replay A/B reference the bfs-vs-scan ratio "
        "prices; the BFS lanes are gated",
    "serve_offered_rows_per_sec":
        "the open-loop load generator's OFFERED rate (an input, not an "
        "outcome); serve_rows_per_sec gates the sustained rate",
    "ckpt_on_iters_per_sec":
        "component of the gated ckpt_overhead_pct difference",
    "ckpt_off_iters_per_sec":
        "component of the gated ckpt_overhead_pct difference",
    "repeats_dropped":
        "bench-harness bookkeeping (outlier repeats), not a serving "
        "contract",
    "ckpt_dropped":
        "latest-wins snapshot replacement is the async writer's "
        "DESIGNED backpressure, not a loss",
    "trace_wall_p99_us":
        "the flight-recorder ring's exact per-event p99, a cross-check "
        "of the sketch-computed serve_p99_us LATENCY lane (agreement is "
        "asserted in-bench within bucket resolution)",
}

# name shapes that mark a bench emission gateable, and the perf_gate
# set that must carry it
_D2_MORPHOLOGY = (
    (("_rows_per_sec", "_iters_per_sec"), "rate"),
    (("_spread",), "spread"),
    (("_p99_us",), "latency"),
    (("_recompiles", "_dropped", "_misscored"), "zero"),
    (("_restore_exact",), "true"),
)


def _string_constants(src: str) -> Set[str]:
    return {n.value for n in ast.walk(ast.parse(src))
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


def recorded_round_keys(paths_to_json: Dict[str, str]) -> Set[str]:
    """Every top-level key of the recorded rounds (``parsed`` unwrapped),
    so historical keys keep their gates even if bench.py moved on."""
    keys: Set[str] = set()
    for _path, text in paths_to_json.items():
        try:
            data = json.loads(text)
        except ValueError:
            continue
        if not isinstance(data, dict):
            continue
        keys.update(data)
        if isinstance(data.get("parsed"), dict):
            keys.update(data["parsed"])
    return keys


def check_perf_gate_coverage(gate_sets: dict, bench_src: str,
                             entry_src: str = "",
                             recorded_keys: Optional[Set[str]] = None,
                             gate_path: str = "scripts/perf_gate.py",
                             bench_path: str = "bench.py",
                             informational: Optional[Dict[str, str]] =
                             None) -> List[Finding]:
    """D2 both directions.  ``gate_sets`` carries perf_gate's four key
    collections (the driver imports the real module; tests hand in
    synthetic ones and their own ``informational`` allowlist)."""
    informational = (D2_INFORMATIONAL if informational is None
                     else informational)
    recorded = recorded_keys or set()
    emitted = _string_constants(bench_src)
    emitted_anywhere = emitted | (_string_constants(entry_src)
                                  if entry_src else set()) | recorded
    rate = tuple(gate_sets.get("RATE_KEYS", ()))
    latency = tuple(gate_sets.get("LATENCY_KEYS", ()))
    zero = tuple(gate_sets.get("ABSOLUTE_ZERO_KEYS", ()))
    true_ = tuple(gate_sets.get("ABSOLUTE_TRUE_KEYS", ()))
    findings: List[Finding] = []

    gate_src = gate_sets.get("_source", "")
    all_gate_keys = ([k for k, _s in rate] + [k for k, _s in latency]
                     + [k for k, _d in zero] + [k for k, _d in true_]
                     + [s for _k, s in rate] + [s for _k, s in latency])
    for key in sorted(set(all_gate_keys)):
        if key not in emitted_anywhere:
            findings.append(Finding(
                "D2", gate_path, _line_of(gate_src, key), "perf_gate",
                key,
                "gate key emitted by neither bench.py/__graft_entry__.py "
                "nor any recorded BENCH_r*/MULTICHIP_r* round — a stale "
                "gate key silently gates nothing"))

    gated = {
        "rate": {k for k, _s in rate},
        "spread": {s for _k, s in rate} | {s for _k, s in latency},
        "latency": {k for k, _s in latency},
        "zero": {k for k, _d in zero},
        "true": {k for k, _d in true_},
    }
    for key in sorted(emitted):
        if key.startswith("_"):
            continue          # a bare suffix literal used to BUILD keys
        for suffixes, kind in _D2_MORPHOLOGY:
            if not any(key.endswith(sfx) and key != sfx
                       for sfx in suffixes):
                continue
            if key in gated[kind] or key in informational:
                continue
            findings.append(Finding(
                "D2", bench_path, _line_of(bench_src, key), "bench",
                key,
                "bench.py emits a %s-shaped key that perf_gate's %s set "
                "does not read and the informational allowlist does not "
                "justify — the lane is measured but ungated"
                % (kind, {"rate": "RATE_KEYS", "spread":
                          "RATE_KEYS/LATENCY_KEYS spread",
                          "latency": "LATENCY_KEYS",
                          "zero": "ABSOLUTE_ZERO_KEYS",
                          "true": "ABSOLUTE_TRUE_KEYS"}[kind])))
            break
    # an informational-allowlist entry matching no emission is itself
    # stale (same contract as the baseline's stale-suppression report)
    for key in sorted(informational):
        if key not in emitted_anywhere:
            findings.append(Finding(
                "D2", bench_path, 0, "bench", key,
                "D2_INFORMATIONAL allowlist entry matches no emitted or "
                "recorded key — remove or re-justify"))
    return findings


# ----------------------------------------------------------------- D3

# free-form / externally-validated knobs: parse-time validation is
# impossible or lives in the component the value selects — each entry
# carries the written justification (printed into the finding when a
# knob drifts ONTO this list without one)
D3_FREEFORM = {
    "data": "required input path; the loader fatals on a missing/"
            "unreadable file (parser.create_parser)",
    "valid_data": "comma list of paths; each load fatals like data",
    "output_model": "output path; open() failure surfaces at write",
    "input_model": "model path; GBDT.from_model_file fatals on junk",
    "output_result": "output path; open() failure surfaces at write",
    "input_init_score": "side-file path; loader fatals on junk",
    "profile_dir": "output directory for jax.profiler traces",
    "metrics_out": "JSONL sink path; telemetry disables the sink loudly "
                   "on open failure (never crashes training)",
    "checkpoint_dir": "directory; write_checkpoint creates it and "
                      "surfaces OSError loudly",
    "label_column": "reference column-selector syntax, resolved (and "
                    "rejected) by io.metadata at load",
    "weight_column": "reference column-selector syntax (as label_column)",
    "group_column": "reference column-selector syntax (as label_column)",
    "ignore_column": "reference column-selector syntax (as label_column)",
    "machine_list_file": "reference-parity option; the TPU bootstrap "
                         "reads env hatches instead",
    "objective": "resolved by objectives.create_objective, which fatals "
                 "on an unknown type",
    "metric": "resolved by metrics.create_metric (unknown names warn "
              "per reference behavior)",
    "predict_buckets": "validated eagerly by predict_bucket_list() "
                       "right after the parse (log.fatal on junk)",
    "label_gain": "parsed by config._parse_label_gain, which log.fatals "
                  "on a malformed double list",
    "device_type": "free-form device selector resolved against "
                   "jax.devices(); mesh construction rejects unknowns",
}

# Config dataclass fields with no knob path BY DESIGN
D3_INTERNAL = {
    "is_parallel": "derived in _check_param_conflict from num_machines/"
                   "tree_learner",
    "is_parallel_find_bin": "derived in _check_param_conflict",
    "tree_config": "nested config dataclass",
    "network_config": "nested config dataclass",
    "io_config": "nested config dataclass",
    "boosting_config": "nested config dataclass",
    "objective_config": "nested config dataclass",
    "metric_config": "nested config dataclass",
}

_TYPED_GETTERS = {"_get_int", "_get_float", "_get_bool"}


def _set_methods(tree: ast.AST):
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef) or not cls.name.endswith(
                "Config"):
            continue
        for item in cls.body:
            if isinstance(item, ast.FunctionDef) and item.name == "set":
                yield cls, item


def _has_loud_call(node: ast.AST) -> bool:
    return any(isinstance(n, ast.Call)
               and _terminal_name(n.func) in ("check", "fatal")
               and _attr_chain(n.func)[:1] == ["log"]
               for n in ast.walk(node))


def collect_knob_census(config_src: str,
                        config_path: str = "lightgbm_tpu/config.py"):
    """Parse config.py: the knob surface (param names read in ``set``
    methods, with how each is validated) and the per-class field sets.

    Returns (params, fields) where ``params`` maps name ->
    {"line", "validated": bool} and ``fields`` maps (class, field) ->
    {"line", "assigned": bool}."""
    tree = ast.parse(config_src, filename=config_path)
    parents = _annotate_parents(tree)
    params: Dict[str, dict] = {}

    def note(name: str, line: int, validated: bool):
        rec = params.setdefault(name, {"line": line, "validated": False})
        rec["validated"] = rec["validated"] or validated

    for _cls, fn in _set_methods(tree):
        # `if "name" in params:` blocks — validated when the If carries a
        # log.check/log.fatal anywhere (body or orelse)
        for node in ast.walk(fn):
            if isinstance(node, ast.If):
                test = node.test
                if (isinstance(test, ast.Compare)
                        and isinstance(test.left, ast.Constant)
                        and isinstance(test.left.value, str)
                        and len(test.ops) == 1
                        and isinstance(test.ops[0], ast.In)
                        and _terminal_name(test.comparators[0])
                        == "params"):
                    note(test.left.value, node.lineno,
                         _has_loud_call(node))
            elif isinstance(node, ast.Call):
                name = _terminal_name(node.func)
                if (name in _TYPED_GETTERS and len(node.args) >= 2
                        and isinstance(node.args[1], ast.Constant)):
                    note(node.args[1].value, node.lineno, True)
                elif (name == "_get_str" and len(node.args) >= 2
                        and isinstance(node.args[1], ast.Constant)):
                    note(node.args[1].value, node.lineno, False)
            elif (isinstance(node, ast.Subscript)
                    and _terminal_name(node.value) == "params"
                    and isinstance(node.slice, ast.Constant)
                    and isinstance(node.slice.value, str)):
                # bare params["x"] read outside an if-in block it already
                # censused — only note, validation decided elsewhere
                note(node.slice.value, node.lineno, False)

    fields: Dict[Tuple[str, str], dict] = {}
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef) or not cls.name.endswith(
                "Config"):
            continue
        assigned = {
            t.attr
            for n in ast.walk(cls)
            if isinstance(n, ast.Assign)
            for t in n.targets
            if isinstance(t, ast.Attribute)
            and _attr_chain(t)[:1] == ["self"]
        }
        for item in cls.body:
            if isinstance(item, ast.AnnAssign) and isinstance(
                    item.target, ast.Name):
                fields[(cls.name, item.target.id)] = {
                    "line": item.lineno,
                    "assigned": item.target.id in assigned,
                }
    return params, fields


def parse_knob_inventory(cli_src: str) -> Dict[str, str]:
    """The ``KNOB_INVENTORY`` dict literal in cli.py (name -> one-line
    description), parsed without importing the module (cli pulls JAX)."""
    tree = ast.parse(cli_src)
    for node in tree.body:
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name)
                        and t.id == "KNOB_INVENTORY"
                        for t in node.targets)
                and isinstance(node.value, ast.Dict)):
            out = {}
            for k, v in zip(node.value.keys, node.value.values):
                if isinstance(k, ast.Constant) and isinstance(
                        v, ast.Constant):
                    out[k.value] = v.value
            return out
    return {}


def check_knob_inventory(config_src: str, cli_src: str,
                         config_path: str = "lightgbm_tpu/config.py",
                         cli_path: str = "lightgbm_tpu/cli.py",
                         freeform: Optional[Dict[str, str]] = None,
                         internal: Optional[Dict[str, str]] = None
                         ) -> List[Finding]:
    """D3: the knob surface vs cli.py's KNOB_INVENTORY + reject paths."""
    freeform = D3_FREEFORM if freeform is None else freeform
    internal = D3_INTERNAL if internal is None else internal
    params, fields = collect_knob_census(config_src, config_path)
    inventory = parse_knob_inventory(cli_src)
    findings: List[Finding] = []
    if not inventory:
        findings.append(Finding(
            "D3", cli_path, 0, "cli", "KNOB_INVENTORY",
            "cli.py has no parseable KNOB_INVENTORY dict literal — the "
            "machine-readable knob inventory is gone"))
        return findings
    for name, rec in sorted(params.items()):
        if name not in inventory:
            findings.append(Finding(
                "D3", config_path, rec["line"], "set", name,
                "config knob read in a *Config.set but missing from "
                "cli.py's KNOB_INVENTORY — undocumented surface"))
        if not rec["validated"] and name not in freeform:
            findings.append(Finding(
                "D3", config_path, rec["line"], "set", name,
                "config knob with neither a typed loud getter, a "
                "log.check/log.fatal in its parse block, nor a "
                "D3_FREEFORM justification — malformed values pass "
                "silently"))
    for name in sorted(inventory):
        if name not in params:
            findings.append(Finding(
                "D3", cli_path, _line_of(cli_src, name), "cli", name,
                "KNOB_INVENTORY entry that no *Config.set reads — "
                "stale knob documentation"))
    for (cls, field), rec in sorted(fields.items()):
        if not rec["assigned"] and field not in internal:
            findings.append(Finding(
                "D3", config_path, rec["line"], cls, field,
                "Config dataclass field that no set()/derivation path "
                "ever assigns and the internal allowlist does not "
                "justify — unreachable configuration"))
    return findings
