"""The engine's trace vocabulary: one table of host spans, one of device
scopes, and the version that makes the scopes reach the chip.

Host spans (`trace.span(name)`) are written twice: as a
`jax.profiler.TraceAnnotation` named SPAN_PREFIX + name, so that they lie
on the profiler's clock beside the device's operations, and on the query's
own `Tracer` (`/v1/query/{id}/trace`).  Device scopes (`kernel_scope`,
`scoped`) are `jax.named_scope`s inside the kernels, under the plan-node
scope the executor opens (`exec/executor.py`, `_exec_node_inner`): they end
up in every HLO instruction's `op_name`, which is how a profile names the
chip's time.  An operation belongs to its INNERMOST `k:`/`x:` scope and to
its OUTERMOST plan-node scope; a fusion takes its root's.

docs/OBSERVABILITY.md quotes both tables (tests/test_trace_scopes.py
compares); benchmarks/span_reduce.py is what reads them from a profile.
"""

from __future__ import annotations

import functools
import re
from typing import Dict

#: TraceAnnotation prefix of the engine's spans ("query:" is the
#: benchmark harness's own and stays its own)
SPAN_PREFIX = "presto:"

#: Part of every jitted program's name (`exec/compile_cache.build_jit`).
#: JAX's persistent compile cache strips debug info before hashing
#: (`strip-debuginfo`), so a program compiled under OTHER scope names would
#: be loaded for this one, old names and all; the function's name is
#: hashed.  Bump it with every change to KERNEL_SCOPES or to where a scope
#: is opened: one vocabulary, one cache key.
SCOPE_VERSION = 2

#: Scopes that arrived after SCOPE_VERSION's last bump, by the plan node
#: under which alone they open -> the mark that the name of a program
#: holding such a node carries behind the plan's fingerprint
#: (`late_scope_marks`).  A bump would change every program's cache key and
#: make every deployment compile everything anew; a mark changes the keys
#: of the programs that open the new scope and of no other.  The next bump
#: of SCOPE_VERSION empties this table.
LATE_SCOPES: Dict[str, str] = {"Window": "w1"}     # k:window

#: The mark behind the fingerprint in the name of every program of the
#: chunk loop (`exec/chunked._FragmentRunner._loop_tag`), which alone opens
#: `k:chunk_scan`: a scope younger than SCOPE_VERSION, like LATE_SCOPES',
#: that no plan node but the loop decides.  Emptied by the same bump.
CHUNK_SCAN_MARK = "c1"


def late_scope_marks(node) -> str:
    """The marks of LATE_SCOPES for the plan under `node`, in the table's
    order; "" for a plan that holds none of its nodes."""
    seen, stack = set(), [node]
    while stack:
        n = stack.pop()
        seen.add(type(n).__name__)
        stack.extend(n.sources)
    return "".join(m for name, m in LATE_SCOPES.items() if name in seen)


#: span name -> (layer, site).  Layers are BENCHMARK.json's.
SPANS: Dict[str, tuple] = {
    "client.post": ("client and protocol",
                    "StatementClient.advance: POST /v1/statement, to the parsed response"),
    "client.get": ("client and protocol",
                   "StatementClient.advance: GET nextUri, to the parsed response"),
    "client.poll_sleep": ("client and protocol",
                          "StatementClient.rows: the sleep before the next poll"),
    "http.post": ("client and protocol", "handler: POST /v1/statement"),
    "http.get": ("client and protocol", "handler: GET /v1/statement/{id}/{token}"),
    "http.submit": ("client and protocol",
                    "handler: server.submit, the job and its thread"),
    "http.grace_wait": ("client and protocol",
                        "handler: the first response waits FIRST_RESPONSE_GRACE_S for the job"),
    "http.long_poll": ("client and protocol",
                       "handler: a poll of an unfinished job waits up to LONG_POLL_S"),
    "http.encode": ("client and protocol",
                    "handler: results_payload, json.dumps and the socket write"),
    "result.rows": ("client and protocol",
                    "_run_job: the result's rows as lists for the pages"),
    "admission.wait": ("serving tier",
                       "_run_job: serving.admit, the wait for a slot of the resource group"),
    "coalesce.window": ("serving tier",
                        "QueryCoalescer._lead: the leader holds the micro-batch window open"),
    "coalesce.ride": ("serving tier",
                      "QueryCoalescer._ride: a rider waits for the leader's launch"),
    "prepared.bind": ("serving tier",
                      "execute_prepared: literals to typed values, template and cache key"),
    "parse": ("planner", "QueryMonitor.phase"),
    "plan": ("planner", "QueryMonitor.phase"),
    "execute": ("executor", "QueryMonitor.phase"),
    "exec.dispatch": ("executor",
                      "run_compiled[_batched], run_distributed: scan batches, parameter "
                      "stacking, the jitted call until it returns"),
    "exec.wait_fetch": ("executor",
                        "run_compiled[_batched]: jax.device_get of the packed result; "
                        "run_distributed: the fetch of the mesh program's guard"),
    "exec.materialize": ("executor",
                         "run_compiled[_batched]: unpack_fetch and materialize_host; "
                         "run_distributed: the result's fetch and its rows"),
    "mesh.feed": ("mesh",
                  "run_distributed: sharded_scan of every scan, cached shards when warm, "
                  "generated on their chips or put there when cold"),
    "xla_compile": ("executor", "compile_cache.Executable.aot_compile: lower + compile"),
    "exec.lower": ("executor",
                   "aot_compile: jaxpr trace + lowering to StableHLO (a first-call build's "
                   "stages reach the Tracer from JAX's events)"),
    "exec.backend": ("executor",
                     "aot_compile: XLA's build, or the load from the persistent cache "
                     "(program=, source=built|loaded)"),
    "exec.data_load": ("data on device",
                       "compile_cache.data_load: a column set born on the device or placed "
                       "there, timed to ready"),
    "chunk.launch": ("executor",
                     "chunked runner: one chunk's program and its compaction, dispatched "
                     "(the host waits here too while the device's queue is full)"),
    "chunk.sync": ("executor",
                   "chunked runner: the host blocked on the device in a loop (chunk 0's "
                   "calibrating compact, the partial aggregate's ratio window, the guards "
                   "after the loop, every chunk of the syncing loop)"),
    "chunk.concat": ("executor", "chunked runner: a loop's buffered parts into one batch"),
}

#: names built at run time (chunked and cluster modes), by their prefix
DYNAMIC_SPANS: Dict[str, tuple] = {
    "fragment f": ("executor", "chunked runner: one fragment's chunk loop or run-once"),
    "pull eid": ("mesh", "cluster worker: one exchange pull (trace_detail=full)"),
}

#: device scope -> what runs under it.  `k:` kernels, `x:` exchanges.
KERNEL_SCOPES: Dict[str, str] = {
    "k:sort": "kernels.sort_pair / sort_perm / sort_values / sort_order_plan / argsort_stable",
    "k:build_probe": "kernels.build_probe: sorted build side, searchsorted probe",
    "k:take_rows.staged": "kernels.take_rows, staged route: sorted indices through exec/gather, one co-sort home",
    "k:take_rows.flat": "kernels.take_rows, flat route: XLA's gather over the packed words (or a column)",
    "k:join_expand": "executor._expanding_join[_static]: a probe row's matches into output slots (repeat, offsets, the build order's gather)",
    "k:runtime_filter": "kernels.rf_build / rf_probe / rf_domain: a join's build keys as a filter on the probe side's scan",
    "k:group_ids": "kernels.group_ids*: dense group ids from a key",
    "k:segment": "kernels.segment_sum / _min / _max / _any",
    "k:fused_group_sums": "kernels.fused_group_sums: the Pallas one-hot matmul aggregate",
    "k:fused_group_sums.operand": "the stack and pad that build fused_group_sums' operand",
    "k:compact": "executor._compact_batch and kernels.compact: live rows to the front",
    "k:scan_filter": "executor._exec_filter: the predicate over a scan's rows, into the selection mask",
    "k:chunk_scan": "chunked runner._scan_builder: a chunk's columns and selection mask generated in its program (the family grid's build_scan)",
    "k:window": "window.execute_window: partition and peer boundaries, frame bounds, the functions' prefix and segmented scans, the gather into sorted order (its sort stays k:sort's)",
    "x:repartition": "parallel/exchange.repartition_batch: one sort by destination (key hash) that carries the columns, the send buffer as contiguous slices, all_to_all",
    "x:all_gather": "parallel/exchange.all_gather_batch: a shard's rows on every shard",
    "x:range_partition": "parallel/exchange.range_partition_batch: sample sort's split, the same send layout by (destination, key), all_to_all",
}


def kernel_scope(name: str):
    """`jax.named_scope(name)` for a name of KERNEL_SCOPES.  Trace-time
    only: a warm program pays nothing."""
    import jax

    if name not in KERNEL_SCOPES:
        raise KeyError(f"{name!r} is not in observe.names.KERNEL_SCOPES")
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: the whole function body under kernel_scope(name)."""
    def deco(fn):
        @functools.wraps(fn)
        def inner(*a, **kw):
            with kernel_scope(name):
                return fn(*a, **kw)
        return inner
    return deco


# ---------------------------------------------------------------------------
# instruction -> op_name, from a compiled program's HLO text
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")


def hlo_op_names(text: str) -> Dict[str, str]:
    """{instruction name: op_name} of one HLO module's text
    (`compiled.as_text()`).  An instruction without metadata that calls a
    computation (a fusion the compiler made) takes that computation's
    ROOT's, and where the root has none either, the op_name most of the
    computation's instructions carry; parameters and constants stay out."""
    own, calls, roots, members, comp = {}, {}, {}, {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
            if comp is not None and "/" in op.group(1):
                members.setdefault(comp, []).append(op.group(1))
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        if m.group(1) and comp is not None:
            roots[comp] = name
    for name, callee in calls.items():
        if name in own:
            continue
        seen = set()
        while callee in roots and callee not in seen:
            seen.add(callee)
            root = roots[callee]
            if root in own:
                own[name] = own[root]
                break
            if callee in members:
                own[name] = max(set(members[callee]),
                                key=members[callee].count)
                break
            callee = calls.get(root)
    return own
