#!/usr/bin/env python
"""Static robustness gate for the coordination-critical runtime layers.

Scans ``paddle_tpu/runtime`` and ``paddle_tpu/distributed/launch`` and
rejects two classes of hang/mask bugs that code review keeps re-admitting:

  1. bare ``except:`` — swallows KeyboardInterrupt/SystemExit and masks the
     very faults the crash-safety layer is supposed to surface;
  2. unbounded ``socket.recv`` — any file that calls ``.recv(...)`` must
     also call ``.settimeout(...)`` somewhere: a recv with no deadline on a
     dead peer is an eternal silent hang (the failure mode the py_store
     hardening exists to rule out);
  3. unguarded reshard collectives — in ``paddle_tpu/distributed/reshard.py``
     every collective/transfer call site (``_constrain``, the jitted-
     identity step executor, and ``jax.device_put``) must sit lexically
     inside a ``with deadline_guard(...)`` block: a collective with a dead
     peer never returns, and the guard is what turns that into a diagnosed
     ``reshard_stall`` instead of a silent fleet-wide hang.
  4. unguarded serving store ops — in ``paddle_tpu/serving`` (router.py,
     worker.py) every coordination-store call (``<store>.set/get/add/
     wait/check/delete_key`` on a receiver whose name mentions "store")
     must sit lexically inside a ``with deadline_guard(...)`` block: the
     router/worker control plane blocks on the store, and an unguarded op
     against a dead store peer is a silent serving outage. Convention:
     store clients in the serving plane are named ``store``/``_store``;
     nothing else (dicts, caches) may use those names.
  5. unguarded transport socket ops — in ``paddle_tpu/serving/transport.py``
     every blocking socket call (``<sock>.send/sendall/recv/accept/
     connect``, plus ``select.select`` polls, on a receiver whose name
     mentions "sock") must sit lexically inside a ``with
     deadline_guard(...)`` block: the streaming dataplane replaces store
     round trips with direct sockets, and an unguarded socket op against
     a wedged peer is the same silent outage rule 4 rules out on the
     store path. Convention: sockets in the transport are named
     ``*sock*`` (``_sock``, ``conn_sock``, ``listen_sock``); nothing
     else may use those names.
  6. unguarded MPMD boundary-queue ops — in ``paddle_tpu/distributed/
     mpmd.py`` every inter-stage queue op (``<chan>.send/poll/recv`` on a
     receiver whose name mentions "chan") must sit lexically inside a
     ``with deadline_guard(...)`` block: a stage whose upstream died
     mid-step would otherwise block on its activation queue forever —
     the exact hang the per-stage failure unit exists to rule out.
     Convention: boundary channel objects are named ``*chan*``
     (``_chan``, ``up_chan``, ``server_chan``); nothing else may use
     those names.
  7. Pallas call sites without an interpret mode for off-TPU runs — in
     ``paddle_tpu/ops/pallas`` every ``pl.pallas_call(...)`` must pass an
     ``interpret=`` keyword: the kernel plane's contract is that tier-1
     runs everywhere (docs/SERVING.md §kernel plane), and a call site
     that hardcodes compiled mode silently breaks every CPU run the
     moment it is reached. Interpret mode is for off-TPU only: on a TPU
     the kernel compiles for real or the program fails, it never gives
     way to interpret mode or to a reference path. The keyword's VALUE
     is the author's choice (typically ``backend != "tpu"``); declaring
     it is not.
  8. supervisor durability — in ``paddle_tpu/distributed/fleet/
     supervisor.py`` (a) every coordination-store op must sit inside a
     ``with deadline_guard(...)`` block (same contract as rule 4: the
     flip state machine blocks on the store during drain, and an
     unguarded op against a dead store peer wedges the control loop);
     and (b) every write-mode ``open(...)`` must live inside the single
     ``_atomic_write_json`` chokepoint, which must itself call
     ``os.replace``: the flip journal is what makes SIGKILL-at-any-
     fence recoverable, so a stray in-place write would reintroduce
     torn-journal states the two-phase protocol exists to rule out.
  9. unjournaled weight flips — the online continuous-learning plane
     (``paddle_tpu/serving``) flips live engine weights only inside the
     journaled weight transaction: (a) ``engine.promote_epoch(...)`` /
     ``engine.discard_shadow(...)`` may only be called from the single
     ``apply_wt_frame`` chokepoint in ``online.py`` — a stray promote
     would swap a shadow buffer no journal fence covers, so a SIGKILL
     there is unrecoverable; and (b) in ``online.py`` building a
     ``swap``/``discard`` wt frame (``encode_wt_frame(..., "swap", ...)``)
     must happen inside a function that also advances or closes the
     weight journal (``advance_weights``/``close_weights``) — the order
     journal-then-order is what lets recovery classify a crash as
     roll-forward or roll-back.

Exit status 0 = clean, 1 = violations (printed one per line as
``path:line: message``). Runs under plain CPython — no third-party deps —
so it can gate CI before any test spins up a backend.
"""
from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_DIRS = [
    os.path.join("paddle_tpu", "runtime"),
    os.path.join("paddle_tpu", "distributed", "launch"),
]

#: files whose collective call sites must run under deadline_guard
GUARDED_FILES = [
    os.path.join("paddle_tpu", "distributed", "reshard.py"),
]

#: call names that ARE collectives/transfers in the guarded files:
#: bare-name calls and attribute calls (obj.<name>) both match
GUARDED_CALLS = {"_constrain", "device_put"}

#: files whose coordination-store ops must run under deadline_guard
GUARDED_STORE_FILES = [
    os.path.join("paddle_tpu", "serving", "router.py"),
    os.path.join("paddle_tpu", "serving", "worker.py"),
    os.path.join("paddle_tpu", "serving", "frontier.py"),
    os.path.join("paddle_tpu", "serving", "replay.py"),
]

#: TCPStore/PyTCPStore client methods that block on the network
STORE_OPS = {"set", "get", "add", "wait", "check", "delete_key"}

#: files whose socket ops must run under deadline_guard (rule 5)
GUARDED_SOCKET_FILES = [
    os.path.join("paddle_tpu", "serving", "transport.py"),
]

#: socket methods that block on the network in the guarded files
#: (create_connection matches via its `socket.` receiver)
SOCKET_OPS = {"send", "sendall", "recv", "recv_into", "accept", "connect",
              "connect_ex", "bind", "listen", "create_connection"}

#: files whose inter-stage boundary-queue ops must run under
#: deadline_guard (rule 6)
GUARDED_CHAN_FILES = [
    os.path.join("paddle_tpu", "distributed", "mpmd.py"),
]

#: channel methods that block on (or feed) the inter-stage wire
CHAN_OPS = {"send", "poll", "recv"}

#: directories whose pallas_call sites must declare interpret= (rule 7)
PALLAS_DIRS = [
    os.path.join("paddle_tpu", "ops", "pallas"),
]

#: files under the supervisor durability contract (rule 8): store ops
#: guarded like rule 4, and journal writes atomic (tmp + os.replace)
GUARDED_SUPERVISOR_FILES = [
    os.path.join("paddle_tpu", "distributed", "fleet", "supervisor.py"),
]

#: the sole function allowed to open files for writing in rule-8 files
ATOMIC_WRITE_FN = "_atomic_write_json"

#: rule 9: the serving package scanned for stray epoch flips, the online
#: module whose journal discipline is checked, and the one function
#: allowed to call the engine's swap/discard methods
WEIGHT_FLIP_DIR = os.path.join("paddle_tpu", "serving")
WEIGHT_FLIP_FILE = os.path.join("paddle_tpu", "serving", "online.py")
WEIGHT_APPLY_FN = "apply_wt_frame"
WEIGHT_FLIP_CALLS = {"promote_epoch", "discard_shadow"}
WEIGHT_JOURNAL_CALLS = {"advance_weights", "close_weights"}


def _py_files(root):
    for d in SCAN_DIRS:
        base = os.path.join(root, d)
        for dirpath, _dirnames, filenames in os.walk(base):
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def check_file(path: str):
    """Yield (line, message) violations for one file."""
    with open(path, "rb") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)

    recv_calls = []
    has_settimeout = False
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            yield (node.lineno,
                   "bare 'except:' — catch specific exceptions; a blanket "
                   "handler masks faults and eats KeyboardInterrupt")
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if node.func.attr == "recv":
                recv_calls.append(node.lineno)
            elif node.func.attr in ("settimeout", "create_connection"):
                # create_connection(timeout=...) also bounds the socket
                has_settimeout = True
    if recv_calls and not has_settimeout:
        for line in recv_calls:
            yield (line,
                   "socket.recv without any settimeout in this file — an "
                   "unbounded recv on a dead peer hangs forever; set a "
                   "deadline (see py_store._recv_msg)")


def _is_deadline_guard_with(node: ast.With) -> bool:
    """True when one of the with-items' context expr is a deadline_guard(...)
    call (bare name or attribute access)."""
    for item in node.items:
        ctx = item.context_expr
        if not isinstance(ctx, ast.Call):
            continue
        f = ctx.func
        if isinstance(f, ast.Name) and f.id == "deadline_guard":
            return True
        if isinstance(f, ast.Attribute) and f.attr == "deadline_guard":
            return True
    return False


def check_guarded_collectives(path: str):
    """Yield (line, message) for collective call sites in a guarded file
    that are not lexically inside a ``with deadline_guard(...)``."""
    with open(path, "rb") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    parent = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parent[child] = node
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = (f.id if isinstance(f, ast.Name)
                else f.attr if isinstance(f, ast.Attribute) else None)
        if name not in GUARDED_CALLS:
            continue
        # the executor's own body (`def _constrain`) holds the cached jit
        # call, not a collective launch; skip call sites inside it
        anc, guarded, in_definition = node, False, False
        while anc in parent:
            anc = parent[anc]
            if isinstance(anc, ast.With) and _is_deadline_guard_with(anc):
                guarded = True
            if (isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and anc.name in GUARDED_CALLS):
                in_definition = True
        if not guarded and not in_definition:
            yield (node.lineno,
                   f"collective call {name!r} outside any `with "
                   "deadline_guard(...)` — a wedged peer makes this hang "
                   "forever with no diagnosis (rule 3, reshard path)")


def _receiver_mentions_store(func: ast.Attribute) -> bool:
    """True when the call receiver is (or dereferences) a name containing
    "store": ``store.get``, ``self._store.set``, ``worker.store.add``."""
    value = func.value
    if isinstance(value, ast.Name):
        return "store" in value.id.lower()
    if isinstance(value, ast.Attribute):
        return "store" in value.attr.lower()
    return False


def check_guarded_store_ops(path: str):
    """Yield (line, message) for serving store ops not lexically inside a
    ``with deadline_guard(...)`` (rule 4)."""
    with open(path, "rb") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    parent = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parent[child] = node
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not (isinstance(func, ast.Attribute) and func.attr in STORE_OPS
                and _receiver_mentions_store(func)):
            continue
        anc, guarded = node, False
        while anc in parent:
            anc = parent[anc]
            if isinstance(anc, ast.With) and _is_deadline_guard_with(anc):
                guarded = True
                break
        if not guarded:
            yield (node.lineno,
                   f"store op .{func.attr}(...) outside any `with "
                   "deadline_guard(...)` — a dead store peer makes the "
                   "serving control plane hang silently (rule 4)")


def _receiver_mentions_sock(func: ast.Attribute) -> bool:
    """True when the call receiver is (or dereferences) a name containing
    "sock": ``raw_sock.recv``, ``self._listen_sock.accept``,
    ``socket.create_connection``."""
    value = func.value
    if isinstance(value, ast.Name):
        return "sock" in value.id.lower()
    if isinstance(value, ast.Attribute):
        return "sock" in value.attr.lower()
    return False


def check_guarded_socket_ops(path: str):
    """Yield (line, message) for transport socket ops not lexically inside
    a ``with deadline_guard(...)`` (rule 5). ``select.select(...)`` polls
    count too — they block when given a nonzero timeout."""
    with open(path, "rb") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    parent = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parent[child] = node
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not isinstance(func, ast.Attribute):
            continue
        is_sock_op = (func.attr in SOCKET_OPS
                      and _receiver_mentions_sock(func))
        is_select = (func.attr == "select"
                     and isinstance(func.value, ast.Name)
                     and func.value.id == "select")
        if not (is_sock_op or is_select):
            continue
        anc, guarded = node, False
        while anc in parent:
            anc = parent[anc]
            if isinstance(anc, ast.With) and _is_deadline_guard_with(anc):
                guarded = True
                break
        if not guarded:
            yield (node.lineno,
                   f"socket op .{func.attr}(...) outside any `with "
                   "deadline_guard(...)` — a wedged transport peer makes "
                   "the streaming dataplane hang silently (rule 5)")


def _receiver_mentions_chan(func: ast.Attribute) -> bool:
    """True when the call receiver is (or dereferences) a name containing
    "chan": ``self._chan.send``, ``up_chan.poll``, ``server_chan.send``."""
    value = func.value
    if isinstance(value, ast.Name):
        return "chan" in value.id.lower()
    if isinstance(value, ast.Attribute):
        return "chan" in value.attr.lower()
    return False


def check_guarded_chan_ops(path: str):
    """Yield (line, message) for MPMD boundary-queue ops not lexically
    inside a ``with deadline_guard(...)`` (rule 6)."""
    with open(path, "rb") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    parent = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parent[child] = node
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not (isinstance(func, ast.Attribute) and func.attr in CHAN_OPS
                and _receiver_mentions_chan(func)):
            continue
        anc, guarded = node, False
        while anc in parent:
            anc = parent[anc]
            if isinstance(anc, ast.With) and _is_deadline_guard_with(anc):
                guarded = True
                break
        if not guarded:
            yield (node.lineno,
                   f"boundary-queue op .{func.attr}(...) outside any "
                   "`with deadline_guard(...)` — a dead upstream stage "
                   "makes this stage hang on its queue forever (rule 6, "
                   "MPMD path)")


def check_pallas_interpret(path: str):
    """Yield (line, message) for ``pallas_call`` sites that do not declare
    an ``interpret=`` keyword (rule 7). Matches bare ``pallas_call(...)``
    and any attribute form (``pl.pallas_call``); a ``**kwargs`` splat
    does NOT count — the choice must be visible at the call site."""
    with open(path, "rb") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = (func.id if isinstance(func, ast.Name)
                else func.attr if isinstance(func, ast.Attribute) else None)
        if name != "pallas_call":
            continue
        if not any(kw.arg == "interpret" for kw in node.keywords):
            yield (node.lineno,
                   "pallas_call without an explicit interpret= keyword — "
                   "every kernel-plane call site must declare its "
                   "interpret mode, which is for off-TPU only (rule 7)")


def _open_mode_is_write(node: ast.Call) -> bool:
    """True when an ``open(...)`` call's literal mode contains w/a/+.
    A non-literal mode counts as a write — the fallback must be visible
    at the call site, same spirit as rule 7."""
    mode = None
    if len(node.args) >= 2:
        mode = node.args[1]
    for kw in node.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if mode is None:
        return False  # open(path) defaults to "r"
    if isinstance(mode, ast.Constant) and isinstance(mode.value, str):
        return any(c in mode.value for c in "wa+x")
    return True


def check_atomic_journal_writes(path: str):
    """Yield (line, message) for rule 8b: write-mode ``open()`` calls in
    a supervisor file outside ``_atomic_write_json``, and an
    ``_atomic_write_json`` that never calls ``os.replace`` (i.e. is not
    actually atomic)."""
    with open(path, "rb") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    parent = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parent[child] = node
    atomic_fn_seen = False
    atomic_fn_has_replace = False
    for node in ast.walk(tree):
        if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name == ATOMIC_WRITE_FN):
            atomic_fn_seen = True
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr == "replace"
                        and isinstance(sub.func.value, ast.Name)
                        and sub.func.value.id == "os"):
                    atomic_fn_has_replace = True
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "open"
                and _open_mode_is_write(node)):
            continue
        anc, inside_atomic = node, False
        while anc in parent:
            anc = parent[anc]
            if (isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and anc.name == ATOMIC_WRITE_FN):
                inside_atomic = True
                break
        if not inside_atomic:
            yield (node.lineno,
                   "write-mode open() outside _atomic_write_json — all "
                   "supervisor journal/roles writes must go through the "
                   "single tmp+os.replace chokepoint (rule 8): an in-place "
                   "write torn by SIGKILL breaks flip recovery")
    if atomic_fn_seen and not atomic_fn_has_replace:
        yield (1,
               "_atomic_write_json never calls os.replace — the write "
               "chokepoint must publish via atomic rename (rule 8)")


def check_weight_flip_confinement(path: str, is_online: bool):
    """Yield (line, message) for rule 9. In every serving file:
    ``<engine>.promote_epoch(...)``/``.discard_shadow(...)`` must sit
    lexically inside ``def apply_wt_frame`` (only possible in online.py).
    In online.py additionally: an ``encode_wt_frame`` call whose literal
    kind is ``"swap"``/``"discard"`` must be inside a function whose body
    also calls ``advance_weights`` or ``close_weights``."""
    with open(path, "rb") as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    parent = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parent[child] = node

    def _enclosing_fn(node):
        anc = node
        while anc in parent:
            anc = parent[anc]
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return anc
        return None

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if (isinstance(func, ast.Attribute)
                and func.attr in WEIGHT_FLIP_CALLS):
            fn = _enclosing_fn(node)
            if fn is None or fn.name != WEIGHT_APPLY_FN:
                yield (node.lineno,
                       f"engine .{func.attr}(...) outside "
                       f"{WEIGHT_APPLY_FN}() — a weight flip not driven "
                       "by a wt frame escapes the journaled transaction, "
                       "so a crash there is unrecoverable (rule 9)")
        if not is_online:
            continue
        name = (func.id if isinstance(func, ast.Name)
                else func.attr if isinstance(func, ast.Attribute)
                else None)
        if name != "encode_wt_frame":
            continue
        kind = node.args[2] if len(node.args) >= 3 else None
        for kw in node.keywords:
            if kw.arg == "kind":
                kind = kw.value
        if not (isinstance(kind, ast.Constant)
                and kind.value in ("swap", "discard")):
            continue
        fn = _enclosing_fn(node)
        journaled = fn is not None and any(
            isinstance(sub, ast.Call)
            and isinstance(sub.func, (ast.Name, ast.Attribute))
            and (sub.func.id if isinstance(sub.func, ast.Name)
                 else sub.func.attr) in WEIGHT_JOURNAL_CALLS
            for sub in ast.walk(fn))
        if not journaled:
            yield (node.lineno,
                   f"wt {kind.value!r} frame built in a function that "
                   "never advances/closes the weight journal — the swap/"
                   "discard order must be journaled first so crash "
                   "recovery can classify it (rule 9)")


def _serving_files(root):
    base = os.path.join(root, WEIGHT_FLIP_DIR)
    if not os.path.isdir(base):
        return
    for dirpath, _dirnames, filenames in os.walk(base):
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def _pallas_files(root):
    for d in PALLAS_DIRS:
        base = os.path.join(root, d)
        if not os.path.isdir(base):
            continue
        for dirpath, _dirnames, filenames in os.walk(base):
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def main(argv=None):
    root = (argv or sys.argv[1:] or [REPO])[0]
    violations = []
    for path in _py_files(root):
        rel = os.path.relpath(path, root)
        for line, msg in check_file(path):
            violations.append(f"{rel}:{line}: {msg}")
    for rel in GUARDED_FILES:
        path = os.path.join(root, rel)
        if not os.path.isfile(path):
            continue
        for line, msg in check_guarded_collectives(path):
            violations.append(f"{rel}:{line}: {msg}")
    for rel in GUARDED_STORE_FILES:
        path = os.path.join(root, rel)
        if not os.path.isfile(path):
            continue
        for line, msg in check_guarded_store_ops(path):
            violations.append(f"{rel}:{line}: {msg}")
    for rel in GUARDED_SOCKET_FILES:
        path = os.path.join(root, rel)
        if not os.path.isfile(path):
            continue
        for line, msg in check_guarded_socket_ops(path):
            violations.append(f"{rel}:{line}: {msg}")
    for rel in GUARDED_CHAN_FILES:
        path = os.path.join(root, rel)
        if not os.path.isfile(path):
            continue
        for line, msg in check_guarded_chan_ops(path):
            violations.append(f"{rel}:{line}: {msg}")
    for path in _pallas_files(root):
        rel = os.path.relpath(path, root)
        for line, msg in check_pallas_interpret(path):
            violations.append(f"{rel}:{line}: {msg}")
    for rel in GUARDED_SUPERVISOR_FILES:
        path = os.path.join(root, rel)
        if not os.path.isfile(path):
            continue
        for line, msg in check_guarded_store_ops(path):
            violations.append(f"{rel}:{line}: {msg}")
        for line, msg in check_atomic_journal_writes(path):
            violations.append(f"{rel}:{line}: {msg}")
    for path in _serving_files(root):
        rel = os.path.relpath(path, root)
        is_online = rel == WEIGHT_FLIP_FILE
        for line, msg in check_weight_flip_confinement(path, is_online):
            violations.append(f"{rel}:{line}: {msg}")
    for v in violations:
        print(v)
    if violations:
        print(f"\n{len(violations)} robustness violation(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
