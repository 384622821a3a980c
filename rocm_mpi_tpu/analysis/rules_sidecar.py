"""GL09 — sidecar atomicity.

The bug shape PRs 4–7 each hardened by hand, once per artifact family:
every schema-versioned sidecar this repo publishes — heartbeat sidecars
(PR 5), checkpoint manifests (PR 1/6), the elastic.jsonl event stream
(PR 6), the tuning cache (PR 7) — is read by an out-of-process consumer
(watchdog, monitor CLI, resume planner, trace-time resolve) that may
observe the file WHILE the writer is mid-write or freshly killed. A
plain `open(path, "w")` + `json.dump` publishes a torn file for that
window, and a torn schema-versioned artifact does not fail loudly: it
bricks the reader at the next real incident (the monitor can't show the
SHRUNK badge, the resume can't plan a mesh, every trace-time lookup
misses forever).

The committed discipline (each writer's docstring says so): **tmp +
rename** (`write to path+".tmp"`, then `os.replace`/`Path.replace` —
readers see old-complete or new-complete, never torn) or **append-only
JSONL** (a torn final line is droppable; every complete line is valid).

What fires: a JSON write — `json.dump(doc, fh)`, `fh.write(
json.dumps(...))`, or `target.write_text(json.dumps(...))` — through a
file opened in `"w"`/`"x"` mode (or a write_text target) whose payload
or path identifies a schema-versioned artifact, when the write is NOT
tmp+rename shaped: the target must be tmp-named (a literal containing
"tmp" somewhere in its derivation, e.g. `path + ".tmp"` /
`with_suffix(".json.tmp")`) AND the same scope must contain a rename
(`os.replace(...)` / `x.replace(...)`). Appends (`"a"` mode) never
fire.

Artifact evidence (both are deliberate, to keep scratch-file writes out
of scope): the dumped payload resolves to a dict literal carrying a
`"schema"`/`"kind"` key or a `"v"`/`"version"` version field, OR the
target path mentions one of the committed artifact families by name.
"""

from __future__ import annotations

import ast
import re

from rocm_mpi_tpu.analysis import astutil
from rocm_mpi_tpu.analysis.core import ModuleContext, Rule

# The committed artifact families (scripts/lint.sh schema-checks these
# names). `quarantine` and `soak-report`
# joined with the request-plane hardening (docs/SERVING.md "SLOs and
# admission"; docs/RESILIENCE.md §8); `fleet` covers the ticket
# journal and the merged fleet report (docs/SERVING.md "The fleet");
# `trace` covers the rmt-trace-report artifact and per-request Chrome
# exports (docs/TELEMETRY.md "Request tracing").
_ARTIFACT_NAME_RE = re.compile(
    r"(heartbeat|manifest|postmortem|bundle|elastic|cache|tuning|"
    r"baseline|findings|summary|quarantine|soak|fleet|trace)"
    r"[-\w.]*\.jsonl?\b"
)

_SCHEMA_KEYS = {"schema", "kind"}
_VERSION_KEYS = {"v", "version"}


def _literal_strings(node: ast.AST):
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            yield n.value
        elif isinstance(n, ast.JoinedStr):
            # Concatenate the literal parts with a placeholder where the
            # interpolations sit, so f"{d}/heartbeat-rank{k}.json" still
            # reads as one artifact name.
            yield "0".join(
                part.value for part in n.values
                if isinstance(part, ast.Constant)
                and isinstance(part.value, str)
            )


def _chase(node: ast.AST, assignments: dict, depth: int = 3) -> ast.AST:
    while depth > 0 and isinstance(node, ast.Name) \
            and node.id in assignments:
        node = assignments[node.id]
        depth -= 1
    return node


def _is_tmpish(node: ast.AST, assignments: dict) -> bool:
    """The target's derivation names a temporary: `path + ".tmp"`,
    `with_suffix(".json.tmp")`, an f-string with a tmp part, or simply a
    name containing 'tmp' (the repo's universal convention)."""
    if isinstance(node, ast.Name) and "tmp" in node.id.lower():
        return True
    chased = _chase(node, assignments)
    return any("tmp" in s.lower() for s in _literal_strings(chased))


def _payload_is_schema_versioned(node: ast.AST, assignments: dict) -> bool:
    chased = _chase(node, assignments)
    if not isinstance(chased, ast.Dict):
        return False
    keys = {
        k.value for k in chased.keys
        if isinstance(k, ast.Constant) and isinstance(k.value, str)
    }
    return bool(keys & _SCHEMA_KEYS) or bool(keys & _VERSION_KEYS)


def _path_is_artifact(node: ast.AST, assignments: dict) -> bool:
    chased = _chase(node, assignments)
    return any(
        _ARTIFACT_NAME_RE.search(s) for s in _literal_strings(chased)
    )


def _open_mode(call: ast.Call) -> str | None:
    """The literal mode of an open()/Path.open() call ('r' default);
    None when the mode is not statically visible. The method form
    (`p.open("w")`) carries the mode in args[0] — the path is the
    receiver, not an argument."""
    if astutil.tail_name(astutil.call_name(call)) != "open":
        return None
    mode_pos = 0 if isinstance(call.func, ast.Attribute) else 1
    if len(call.args) > mode_pos:
        mode_node = call.args[mode_pos]
    else:
        mode_node = astutil.call_kwarg(call, "mode")
    if mode_node is None:
        # open(p) / p.open() with no mode: read
        return "r"
    return astutil.str_const(mode_node)


class _ScopeScan:
    """One function (or module) body's open/write/rename facts."""

    def __init__(self, scope: ast.AST):
        self.assignments: dict[str, ast.AST] = {}
        # fh name -> (mode, path expr, open call)
        self.opens: dict[str, tuple] = {}
        self.renames_present = False
        # (site node, payload expr, target expr or fh name)
        self.json_writes: list[tuple] = []
        self._walk(scope)

    def _walk(self, scope: ast.AST) -> None:
        # One scope at a time: a rename in SOME OTHER function must not
        # legitimize this one's in-place write (each def is scanned as
        # its own scope by check()).
        for node in astutil.walk_no_nested_functions(scope):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                self.assignments[node.targets[0].id] = node.value
                if isinstance(node.value, ast.Call):
                    self._note_open(node.value, node.targets[0].id)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if isinstance(item.context_expr, ast.Call) and \
                            isinstance(item.optional_vars, ast.Name):
                        self._note_open(
                            item.context_expr, item.optional_vars.id
                        )
            elif isinstance(node, ast.Call):
                tail = astutil.tail_name(astutil.call_name(node))
                if tail == "replace":
                    self.renames_present = True
                elif tail == "dump" and len(node.args) >= 2 and \
                        isinstance(node.args[1], ast.Name):
                    self.json_writes.append(
                        (node, node.args[0], node.args[1].id)
                    )
                elif tail == "write" and node.args and \
                        isinstance(node.func, ast.Attribute) and \
                        isinstance(node.func.value, ast.Name):
                    payload = node.args[0]
                    if self._is_json_payload(payload):
                        self.json_writes.append(
                            (node, payload, node.func.value.id)
                        )
                elif tail == "write_text" and node.args and \
                        isinstance(node.func, ast.Attribute):
                    payload = node.args[0]
                    if self._is_json_payload(payload):
                        self.json_writes.append(
                            (node, payload, node.func.value)
                        )

    def _note_open(self, call: ast.Call, name: str) -> None:
        mode = _open_mode(call)
        if mode is None:
            return
        if isinstance(call.func, ast.Attribute):
            path = call.func.value  # p.open(...): the receiver IS the path
        else:
            path = call.args[0] if call.args else None
        self.opens[name] = (mode, path, call)

    @staticmethod
    def _is_json_payload(node: ast.AST) -> bool:
        """json.dumps(...) somewhere in the written expression."""
        for n in ast.walk(node):
            if isinstance(n, ast.Call) and \
                    astutil.tail_name(astutil.call_name(n)) == "dumps":
                return True
        return False


class SidecarAtomicityRule(Rule):
    id = "GL09"
    name = "sidecar-atomicity"
    severity = "error"
    rationale = (
        "schema-versioned sidecars are read by out-of-process consumers "
        "mid-run; a non-atomic writer publishes a torn file that bricks "
        "the reader at the next real incident (the class hand-fixed in "
        "PRs 4-7: heartbeats, manifests, elastic.jsonl, tuning cache)"
    )
    hint = "see docs/ANALYSIS.md#gl09"

    def check(self, ctx: ModuleContext):
        findings = []
        scopes: list = [ctx.tree]
        scopes += [
            n for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        seen = set()
        for scope in scopes:
            scan = _ScopeScan(scope)
            for site, payload, target in scan.json_writes:
                key = (site.lineno, site.col_offset)
                if key in seen:
                    continue
                path_expr = None
                if isinstance(target, str):
                    opened = scan.opens.get(target)
                    if opened is None:
                        continue  # unknown handle — not judged
                    mode, path_expr, _ = opened
                    if not mode or mode[0] not in ("w", "x"):
                        continue  # append/read: the other discipline
                else:
                    path_expr = target  # write_text target
                if path_expr is None:
                    continue
                versioned = _payload_is_schema_versioned(
                    payload, scan.assignments
                ) or _path_is_artifact(path_expr, scan.assignments)
                if not versioned:
                    continue
                compliant = _is_tmpish(path_expr, scan.assignments) \
                    and scan.renames_present
                if compliant:
                    continue
                seen.add(key)
                findings.append(ctx.finding(
                    site, self,
                    "schema-versioned artifact is written in place "
                    "(no tmp+rename, not append-only) — a reader can "
                    "observe the torn file and every consumer of this "
                    "sidecar silently breaks",
                    "write to <path>.tmp and os.replace() it over the "
                    "final path (tuning/cache.write_doc and "
                    "telemetry/aggregate.write_json_atomic are the "
                    "reference writers), or use append-only JSONL",
                ))
        return findings
