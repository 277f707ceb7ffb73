"""payload-budget: whole-payload phases must not inherit the admission
budget — and quick metadata ops must not shed it.

The server runs blocking object-layer work on its executor through two
funnels (server/app.py): `_run` carries the request's deadline Budget
contextvar into the worker (admission/queue-wait semantics apply), and
`_run_nobudget` deliberately drops it.  The split is a correctness
contract, not a style choice:

- A WHOLE-PAYLOAD phase (PUT body consumption, multipart part upload,
  multipart assembly, Select scans, response-chunk pulls) under `_run`
  dies mid-transfer the moment the admission budget — which bounds
  queue wait and time-to-first-byte work, not transfer time — runs out.
  PR 3 established these run `_run_nobudget`; new pipeline stages must
  not silently regress this (ISSUE 5 / ROADMAP analysis follow-up).

- A QUICK METADATA op (object info, delete, upload create/abort) under
  `_run_nobudget` escapes the deadline plane entirely: its RPC hops and
  per-drive gates stand down, so one hung drive stalls the request
  forever instead of shedding at the budget.

The checker matches the callable handed to the funnel by terminal name,
so it sees `self.api.put_object`, a bare `next`, or a bound method alike;
lambdas and locals are out of scope (no interprocedural guessing)."""

from __future__ import annotations

import ast

from ..core import Finding, call_name, rule, terminal_name

#: callables that consume or produce a request's whole payload: these
#: must ride `_run_nobudget` (killing them mid-body corrupts/aborts a
#: transfer the admission budget was never meant to bound)
WHOLE_PAYLOAD = frozenset({
    "put_object", "put_object_part", "complete_multipart_upload",
    "run_select", "next",
})

#: quick metadata ops: bounded work that MUST stay under the deadline
#: plane (`_run`) so a hung drive sheds instead of hanging the request
FAST_METADATA = frozenset({
    "get_object_info", "open_object", "new_multipart_upload",
    "abort_multipart_upload", "delete_object", "delete_objects",
    "list_object_parts", "bucket_exists", "list_buckets", "make_bucket",
    "delete_bucket",
})


@rule("payload-budget",
      "whole-payload phases (put_object/next/...) belong on _run_nobudget;"
      " quick metadata ops belong on _run — the admission budget must "
      "bound queue wait, not transfers")
def check(module, project):
    out = []
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        funnel = call_name(node).rsplit(".", 1)[-1]
        if funnel not in ("_run", "_run_nobudget"):
            continue
        target = terminal_name(node.args[0])
        if not target:
            continue  # lambdas/computed callables: out of scope
        if funnel == "_run" and target in WHOLE_PAYLOAD:
            out.append(Finding(
                module.path, node.lineno, node.col_offset,
                "payload-budget",
                f"whole-payload phase `{target}` runs under _run: the "
                "admission budget kills it mid-transfer — use "
                "_run_nobudget (see PR 3's deadline-plane contract)"))
        elif funnel == "_run_nobudget" and target in FAST_METADATA:
            out.append(Finding(
                module.path, node.lineno, node.col_offset,
                "payload-budget",
                f"metadata op `{target}` runs under _run_nobudget: it "
                "escapes the deadline plane (drive gates/RPC clamps "
                "stand down) — use _run"))
    return out
