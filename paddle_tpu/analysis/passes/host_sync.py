"""host-sync: implicit device->host transfers on serving/training hot
paths must be explicit.

A `.item()`, `np.asarray(...)`, `float(arr[i])` or `block_until_ready`
in the decode loop stalls the dispatch pipeline: under async dispatch a
step call returns in ~2ms while the device works 120ms (measured in
PR 10), so one stray sync per round can halve tokens/sec and never
shows up in a profile as anything but "python".

This pass does NOT ban syncs — emitting a token IS a d2h read. It bans
*unannotated* syncs inside the configured hot scopes: every site must
carry `# paddle-lint: disable=host-sync -- <why this sync is required>`
so the set of pipeline stalls on the hot path is reviewable in one grep.

Hot scopes (path -> enclosing-qualname prefixes): the serving engine
step/decode/prefill/admission loop and jit.TrainStep.__call__.
"""
from __future__ import annotations

import ast
from typing import List, Set

from ..core import AnalysisPass, Finding, SourceFile, enclosing_scope, \
    register_pass
from . import _util

#: path suffix -> qualname prefixes that form the hot set
HOT_SCOPES = {
    'paddle_tpu/serving/engine.py': (
        'InferenceEngine.step', 'InferenceEngine.run',
        'InferenceEngine._decode_round', 'InferenceEngine._spec_round',
        'InferenceEngine._emit_round',
        'InferenceEngine._admit', 'InferenceEngine._begin_request',
        'InferenceEngine._whole_prefill', 'InferenceEngine._advance_prefills',
        'InferenceEngine._prefill_chunk', 'InferenceEngine._activate',
        'InferenceEngine._draft_prefill', 'InferenceEngine._retire',
    ),
    'paddle_tpu/jit/__init__.py': ('TrainStep.__call__',),
    # the hot-swap path runs INTERLEAVED with live decode rounds (the
    # drain keeps the fleet serving), so a stray sync here stalls the
    # same pipeline the engine scopes protect; the publisher's snapshot
    # is the one sanctioned bulk d2h and must say so
    'paddle_tpu/serving/hotswap.py': (
        'WeightStore.publish', 'WeightPublisher.', 'ReplicaUpdater.',
        'CanaryGate.__call__', 'finite_weights_gate', '_host_tree',
    ),
    'paddle_tpu/loop/rollout.py': (
        'RolloutLoop.', 'RolloutBatch.', 'Rollout.',
    ),
    # the autoscaler's poll loop and the loadgen replayer both run
    # INTERLEAVED with decode rounds (one poll/submit pass per router
    # step) — a stray sync in either stalls the same pipeline the
    # engine scopes protect
    'paddle_tpu/serving/autoscaler.py': ('Autoscaler.',),
    'paddle_tpu/loadgen/replay.py': ('LoadReplayer.',),
    # the page manager (ISSUE 16) runs INSIDE the admission/decode loop:
    # reserve/attach/COW on every seating, note_written every round. Its
    # bookkeeping is host-side numpy BY DESIGN — any device read that
    # creeps in (e.g. materializing a page to inspect it) stalls every
    # decode round, so the whole class is a hot scope
    'paddle_tpu/serving/kv_pool.py': ('PagedSlotPool.',),
    # the cross-process RPC client (ISSUE 18) runs INSIDE the router
    # step loop: every placement reads the mirror scheduler and every
    # step applies mirror updates. The mirrors are plain-python BY
    # DESIGN (tokens are ints off the wire) — a device read creeping in
    # here (e.g. materializing arrays while building a frame) stalls
    # the routing of every replica, remote or not
    'paddle_tpu/serving/remote.py': ('RemoteReplica.', '_MirrorScheduler.',
                                     'RpcClient.'),
    # the supervisor's monitoring pass interleaves with router steps in
    # the serving loop; its state machine is pidfiles + clocks only —
    # any device sync in poll/heartbeat stalls serving fleet-wide
    'paddle_tpu/serving/supervisor.py': ('Supervisor.poll',
                                         'Supervisor._poll',
                                         'Supervisor._on_death',
                                         'Supervisor._backoff_s'),
    # the adapter bank (ISSUE 19) is consulted INSIDE the admission/
    # decode loop: pin/unpin on every request boundary, device_arrays()
    # per jit call. Its slot table is host-side python BY DESIGN — the
    # one sanctioned device op is the `.at[slot].set` hot-load in
    # _write_slot (a device-side scatter, not a sync); anything reading
    # factors back (np.asarray on a bank, .item on a scale) stalls
    # every decode round, so the whole class is a hot scope. The
    # trace-time apply hook runs inside the COMPILED program where a
    # sync is a tracer error, but np.asarray there would silently
    # constant-fold a weight into the executable — equally banned
    'paddle_tpu/serving/adapters/bank.py': ('AdapterBank.',),
    'paddle_tpu/serving/adapters/apply.py': ('linear_hook',
                                             'adapter_scope.'),
    # the request ledger (ISSUE 20) is written from INSIDE the engine
    # step / router failover loops: queue transitions at every
    # scheduler pass, per-round fair-share attribution after every
    # decode round, finalize on every retire. Its books are host-side
    # floats BY DESIGN — any device read creeping into add()/
    # note_round()/finalize_record() stalls every decode round of
    # every request, which is exactly the tail it exists to explain
    'paddle_tpu/observability/reqledger.py': ('RequestRecord.',
                                              'RequestLedger.'),
}

_NP_ROOTS = frozenset(('np', 'numpy', 'onp'))
_SYNC_METHODS = frozenset(('item', 'tolist', 'block_until_ready'))


@register_pass
class HostSyncPass(AnalysisPass):
    name = 'host-sync'
    description = ('implicit device->host transfers (np.asarray, .item, '
                   '.tolist, block_until_ready, int()/float() on array '
                   'reads) on serving/train-step hot paths without an '
                   'explicit justification annotation')

    def visit_file(self, sf: SourceFile) -> List[Finding]:
        prefixes = None
        for suffix, pref in HOT_SCOPES.items():
            if sf.rel.endswith(suffix):
                prefixes = pref
                break
        if prefixes is None:
            return []
        findings: List[Finding] = []
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            scope = enclosing_scope(node)
            if not scope.startswith(tuple(prefixes)):
                continue
            msg = self._sync_kind(node)
            if msg:
                findings.append(self.finding(
                    sf, node,
                    f'{msg} in hot scope `{scope}` — a device sync here '
                    f'stalls the dispatch pipeline; hoist it off the hot '
                    f'path or annotate the site with '
                    f'`# paddle-lint: disable=host-sync -- <why>`'))
        return findings

    def _sync_kind(self, node: ast.Call) -> str:
        full = _util.call_name(node) or ''
        seg = _util.last_segment(full)
        root = full.split('.', 1)[0]
        if seg in ('asarray', 'array') and root in _NP_ROOTS:
            return f'`{full}()` forces a device->host copy'
        if full == 'jax.device_get':
            return '`jax.device_get()` forces a device->host copy'
        if seg in _SYNC_METHODS and isinstance(node.func, ast.Attribute):
            return f'`.{seg}()` blocks on the device'
        if full in ('int', 'float', 'bool') and node.args and \
                self._reads_array(node.args[0]):
            return (f'`{full}(...)` on an array element forces a '
                    f'device->host read')
        return ''

    @staticmethod
    def _reads_array(expr: ast.AST) -> bool:
        """int(x[i]) / float(row[j]) style: a subscript read is the usual
        shape of pulling one element off the device."""
        return any(isinstance(n, ast.Subscript) for n in ast.walk(expr))
