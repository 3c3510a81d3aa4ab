"""Replica process entrypoint: `python -m paddle_tpu.serving.replica_main`.

One invocation = one `InferenceEngine` in its own OS process, serving
the framed RPC protocol from `serving.remote` on an AF_UNIX socket.
The supervisor spawns this module; the parent talks to it through a
`RemoteReplica`. Startup contract (the warm-start guarantee the
fleet_proc tier-1 guard measures):

1. `programs.configure(<store dir>)` BEFORE the engine is built, so
   every serving program loads from the ProgramStore persistent tier
   (StableHLO + jax's persistent compile cache, which
   `programs.ensure_compile_cache` places) — a new process LOADS, it
   never compiles. Ready-marks of `paddle_jit_compiles_total` /
   `paddle_jit_cache_hits_total` are snapshotted once startup settles
   and shipped in `stats`, so the parent can assert the serving
   window's compile delta equals its cache-hit delta.
2. Weights come from the stale-writer-safe `WeightStore` (sha256
   verified at read) — the factory builds the ARCHITECTURE, the store
   provides the numbers, `swap_weights` stamps the version. No weight
   bytes ever cross the RPC socket.
3. The PR-17 `Shipper` starts last: metrics/events/spans spool to disk
   and the parent's Aggregator stitches them into the fleet view.

SIGTERM honors the existing graceful-drain path (PreemptionHandler →
engine.drain under the deadline → exit 0); the supervisor classifies
exit codes: 0 clean, 2 usage, 3 load failure, anything else a crash.

The model factory is addressed as `module:callable` or
`/path/to/file.py:callable` (tests and bench point at their own tiny
factories without packaging them); it must return a constructed Layer
(eval mode is applied here).
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time
import traceback
from typing import Any, Dict, Optional

EXIT_CLEAN = 0
EXIT_CRASH = 1
EXIT_USAGE = 2
EXIT_LOAD = 3


def _resolve_factory(spec: str):
    """`pkg.mod:fn` or `/path/file.py:fn` -> the callable."""
    target, sep, fn_name = spec.rpartition(':')
    if not sep or not target or not fn_name:
        raise ValueError(
            f'model spec must be "module:callable" or "file.py:callable", '
            f'got {spec!r}')
    if target.endswith('.py') or os.sep in target:
        import importlib.util
        mod_spec = importlib.util.spec_from_file_location(
            'paddle_tpu_replica_factory', target)
        if mod_spec is None or mod_spec.loader is None:
            raise ImportError(f'cannot load factory file {target!r}')
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
    else:
        import importlib
        mod = importlib.import_module(target)
    fn = getattr(mod, fn_name, None)
    if fn is None:
        raise ImportError(f'{target!r} has no attribute {fn_name!r}')
    return fn


class _ReplicaServer:
    """Accept loop + per-connection dispatch threads over one engine.

    Engine-touching methods serialize on `_elock`; `healthz` answers
    WITHOUT it, by design — that is what lets the supervisor's
    heartbeat distinguish "busy decoding" (healthz answers) from
    "SIGSTOPped / wedged" (socket times out)."""

    def __init__(self, engine, listener: socket.socket, *,
                 weight_store=None, preempt=None,
                 drain_deadline_s: float = 30.0, uid: str = ''):
        from .. import observability as _obs
        from ..analysis.runtime import concurrency as _concurrency
        self._obs = _obs
        self.engine = engine
        self.listener = listener
        self.weight_store = weight_store
        self.preempt = preempt
        self.drain_deadline_s = drain_deadline_s
        self.uid = uid
        self._elock = _concurrency.RLock('_ReplicaServer._elock')
        self._requests: Dict[int, Any] = {}   # rid -> engine handle
        self._final_sent: set = set()
        self._stop = threading.Event()
        self._drained = False
        # ready-marks: compile counters once startup settled — the
        # warm-start guard's zero point
        reg = _obs.get_registry()
        self.marks = {
            'jit_compiles_at_ready': reg.value('paddle_jit_compiles_total'),
            'jit_cache_hits_at_ready':
                reg.value('paddle_jit_cache_hits_total'),
        }

    # -- request mirror bookkeeping ---------------------------------------
    def _updates(self) -> Dict[str, Any]:
        """Status/token deltas for every tracked request; a request's
        terminal status ships until the frame carrying it is SENT (the
        caller prunes after a successful send, so a torn response frame
        re-ships the final state on the next step)."""
        out = {}
        for rid, h in self._requests.items():
            upd: Dict[str, Any] = {'status': h.status,
                                   'tokens': list(h.tokens)}
            if h.weight_version is not None:
                upd['weight_version'] = h.weight_version
            if getattr(h, 'adapter_version', None) is not None:
                upd['adapter_version'] = h.adapter_version
            if h.error is not None:
                from ..resilience.retry import is_transient
                upd['error'] = {
                    'type': type(h.error).__name__,
                    'message': str(h.error),
                    'transient': is_transient(h.error),
                }
            out[str(rid)] = upd
        return out

    def _prune_done(self):
        for rid in [r for r, h in self._requests.items() if h.done]:
            if rid in self._final_sent:
                del self._requests[rid]
                self._final_sent.discard(rid)
            else:
                self._final_sent.add(rid)

    # -- RPC methods -------------------------------------------------------
    def rpc_hello(self, **_):
        eng = self.engine
        return {
            'pid': os.getpid(), 'uid': self.uid,
            'weight_version': eng.weight_version,
            'prefill_chunk_tokens': eng.prefill_chunk_tokens,
            'num_slots': eng.pool.num_slots,
            'max_length': eng.pool.max_length,
        }

    def rpc_submit(self, prompt_tokens=None, params=None, priority=None,
                   adapter_id=None, **_):
        from .remote import params_from_wire
        with self._elock:
            h = self.engine.submit(prompt_tokens,
                                   params=params_from_wire(params or {}),
                                   priority=priority,
                                   adapter_id=adapter_id)
            self._requests[h.request_id] = h
            return {'rid': h.request_id, 'status': h.status}

    def rpc_step(self, **_):
        with self._elock:
            t0 = time.perf_counter()
            progressed = self.engine.step() if self.engine.has_work else 0
            # reported so the parent's mirror ledger can split this
            # round into decode (child wall) vs rpc_transport (framing
            # + socket surplus measured around the call)
            step_wall = time.perf_counter() - t0
            out = {'progressed': progressed, 'updates': self._updates(),
                   'step_wall_s': step_wall}
            self._prune_done()
            return out

    def rpc_evict_all(self, **_):
        with self._elock:
            orphans = self.engine.evict_all()
            rids = [h.request_id for h in orphans]
            for rid in rids:
                self._requests.pop(rid, None)
                self._final_sent.discard(rid)
            return {'rids': rids}

    def rpc_begin_drain(self, **_):
        with self._elock:
            self.engine.begin_drain()
            return {'draining': True}

    def rpc_drain(self, deadline_s=None, **_):
        with self._elock:
            ok = self.engine.drain(deadline_s=deadline_s)
            out = {'ok': ok, 'updates': self._updates()}
            self._prune_done()
            return out

    def rpc_swap_weights(self, version=None, strict=True, **_):
        if self.weight_store is None:
            raise RuntimeError('replica process has no --weight-store; '
                               'cannot swap by version')
        with self._elock:
            prev_version = self.engine.weight_version
            state = self.weight_store.load(int(version))
            self.engine.swap_weights(state, version=int(version),
                                     strict=bool(strict))
            return {'weight_version': self.engine.weight_version,
                    'prev_version': prev_version}

    def rpc_healthz(self, **_):
        # NO engine lock: must answer while a decode block runs
        return {'ok': True, 'pid': os.getpid(), 'uid': self.uid,
                'draining': self.engine.draining,
                'weight_version': self.engine.weight_version,
                'states': sorted(self._obs.degraded_states().keys())}

    def rpc_stats(self, **_):
        reg = self._obs.get_registry()
        with self._elock:
            out = self.engine.stats()
        out['jit_compiles_total'] = reg.value('paddle_jit_compiles_total')
        out['jit_cache_hits_total'] = reg.value(
            'paddle_jit_cache_hits_total')
        out.update(self.marks)
        out['pid'] = os.getpid()
        out['uid'] = self.uid
        return out

    def rpc_set_obs_scope(self, scope=None, **_):
        self.engine.obs_scope = scope
        return {'scope': scope}

    def rpc_shutdown(self, **_):
        self._stop.set()
        return {'stopping': True}

    # -- serve loop --------------------------------------------------------
    def _dispatch(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        from ..resilience.retry import is_transient
        method = msg.get('method', '')
        fn = getattr(self, f'rpc_{method}', None)
        if fn is None:
            return {'error': {'type': 'KeyError',
                              'message': f'unknown RPC method {method!r}',
                              'transient': False}}
        try:
            return {'result': fn(**(msg.get('args') or {}))}
        except BaseException as exc:   # ships to the caller, typed
            return {'error': {'type': type(exc).__name__,
                              'message': str(exc),
                              'transient': is_transient(exc)}}

    def _serve_conn(self, conn: socket.socket):
        from .remote import recv_msg, send_msg
        try:
            while not self._stop.is_set():
                try:
                    msg = recv_msg(conn)
                except (ConnectionError, OSError, TimeoutError):
                    return   # peer gone; its mirrors survive parent-side
                send_msg(conn, self._dispatch(msg))
        finally:
            try:
                conn.close()
            except OSError:
                self._obs.count_suppressed('replica_conn_close')

    def serve_forever(self):
        """Accept until shutdown RPC or SIGTERM; then drain and return.
        Returns True when the drain (if any) beat its deadline."""
        self.listener.settimeout(0.2)
        threads = []
        while not self._stop.is_set():
            if self.preempt is not None and self.preempt.requested:
                break
            try:
                conn, _ = self.listener.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True,
                                 name='replica-rpc-conn')
            t.start()
            threads.append(t)
        # graceful exit: finish every accepted request under the deadline
        with self._elock:
            ok = True
            if self.engine.has_work or not self.engine.draining:
                ok = self.engine.drain(deadline_s=self.drain_deadline_s)
            self._drained = True
        self._stop.set()
        return ok


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog='python -m paddle_tpu.serving.replica_main',
        description='one supervised InferenceEngine replica process')
    p.add_argument('--socket', required=True,
                   help='AF_UNIX socket path to serve the replica RPC on')
    p.add_argument('--model-spec', required=True,
                   help='model factory: "module:callable" or '
                        '"/path/file.py:callable"')
    p.add_argument('--model-kwargs', default='{}',
                   help='JSON kwargs for the model factory')
    p.add_argument('--engine-kwargs', default='{}',
                   help='JSON kwargs for InferenceEngine')
    p.add_argument('--program-store', default=None,
                   help='ProgramStore directory (warm-start tier)')
    p.add_argument('--weight-store', default=None,
                   help='WeightStore directory (the weight plane)')
    p.add_argument('--weight-version', type=int, default=None,
                   help='version to load at boot (default: latest)')
    p.add_argument('--spool', default=None,
                   help='observability spool dir: starts a Shipper')
    p.add_argument('--uid', default='',
                   help='process uid for spool segments / pidfiles')
    p.add_argument('--obs-scope', default=None)
    p.add_argument('--drain-deadline-s', type=float, default=30.0)
    p.add_argument('--heartbeat-file', default=None,
                   help=argparse.SUPPRESS)   # reserved
    return p


def main(argv=None) -> int:
    try:
        opts = _build_parser().parse_args(argv)
        model_kwargs = json.loads(opts.model_kwargs)
        engine_kwargs = json.loads(opts.engine_kwargs)
        factory = _resolve_factory(opts.model_spec)
    except SystemExit:
        return EXIT_USAGE
    except Exception:
        traceback.print_exc()
        return EXIT_USAGE

    # program store FIRST: the engine's build-time preload must hit the
    # persistent tier, not the compiler
    try:
        from .. import programs
        # one compile cache for every replica process, placed by
        # JAX_COMPILATION_CACHE_DIR or in-checkout — never per store dir
        programs.ensure_compile_cache()
        if opts.program_store:
            programs.configure(opts.program_store)
        model = factory(**model_kwargs)
        model.eval()
        from .engine import InferenceEngine
        engine = InferenceEngine(model, **engine_kwargs)
        weight_store = None
        if opts.weight_store:
            from .hotswap import WeightStore
            weight_store = WeightStore(opts.weight_store)
            version = (opts.weight_version
                       if opts.weight_version is not None
                       else weight_store.latest_version())
            if version is not None:
                state = weight_store.load(int(version))
                engine.swap_weights(state, version=int(version))
    except Exception:
        traceback.print_exc()
        return EXIT_LOAD

    if opts.obs_scope:
        engine.obs_scope = opts.obs_scope

    # warm the incidental non-store programs (host<->device converts)
    # before the ready-marks snapshot, mirroring bench coldstart: the
    # serving-window compile delta must isolate store-owned executables
    import jax.numpy as jnp
    import numpy as np
    _ = np.asarray(jnp.asarray([1, 2, 3], jnp.int32))
    _ = float(np.asarray(jnp.asarray(0.0, jnp.float32)))

    preempt = engine.enable_graceful_drain(
        deadline_s=opts.drain_deadline_s)

    shipper = None
    if opts.spool:
        from ..observability.shipper import Shipper
        shipper = Shipper(opts.spool, interval_s=0.5,
                          uid=opts.uid or None)
        shipper.start()

    # bind LAST: a connectable socket is the readiness signal the
    # supervisor polls for, so it must imply "warm and serviceable"
    listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        if os.path.exists(opts.socket):
            os.unlink(opts.socket)   # stale tenant of our own path
        listener.bind(opts.socket)
        listener.listen(8)
    except OSError:
        traceback.print_exc()
        return EXIT_LOAD

    server = _ReplicaServer(engine, listener,
                            weight_store=weight_store, preempt=preempt,
                            drain_deadline_s=opts.drain_deadline_s,
                            uid=opts.uid)
    try:
        server.serve_forever()
    finally:
        try:
            listener.close()
            if os.path.exists(opts.socket):
                os.unlink(opts.socket)
        except OSError:
            pass
        if shipper is not None:
            shipper.stop(flush=True)
    return EXIT_CLEAN


if __name__ == '__main__':
    sys.exit(main())
