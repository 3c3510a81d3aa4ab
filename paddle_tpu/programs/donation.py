"""Donation gauntlet: probe-and-enable buffer donation for store-served
programs.

PR 8 discovered that store-served executables (jax.export StableHLO
payloads re-compiled through ``jax.jit(exported.call)``) intermittently
HEAP-CORRUPTED when donation was re-applied on jaxlib 0.4.36, the
runtime of that time — segfaults and garbage losses on roughly half of
14-run gauntlets. The store ran every persisted program UNDONATED
after that: memory-safe, but every serving pool op paid a full
pool-buffer round trip and donated train state transiently 2x-buffered.
What the runtime installed today does is the probe's answer, recorded
per backend fingerprint (CHANGES.md PR 21 has jaxlib 0.9.0's).

This module replaces the hardcoded posture with a *probe*: at
ProgramStore init (when a persistent directory is configured) a
subprocess-isolated gauntlet compiles a small donated store-served
executable — export → serialize → deserialize → ``jax.jit(call,
donate_argnums)`` → AOT compile, the exact code path the store uses —
and re-runs it against an undonated reference of the same exported
module. Bitwise-equal, finite outputs across every run classify the
installed runtime ``safe``; a mismatch, a non-finite value, a non-zero
exit (the probe segfaulting must never take the trainer with it — hence
the subprocess), or a timeout classify it ``corrupting``. The verdict is
manifest-recorded per backend fingerprint in the store directory, so a
jaxlib upgrade flips donation back on with zero code change, and a
process-level cache keeps re-inits from re-probing.

On a ``safe`` verdict the store re-applies each program's recorded
``donate_argnums`` and guards the first K post-enablement invocations
with corruption sentinels (finiteness spot-checks on the outputs, run
against snapshot copies of the donated inputs so a trip can re-run
undonated). A tripped sentinel QUARANTINES donation for this
fingerprint — verdict file flipped, donated executables dropped and
recompiled undonated, ``donation_quarantined`` emitted (a
flight-recorder trigger) — and the triggering call re-runs undonated,
so a garbage value is never surfaced.

One client per chip: a TPU belongs to one process at a time, so a probe
child spawned from the process that holds the chip cannot attach — it
would hang to its timeout and record a false ``corrupting``. When the
parent's backend is a TPU and no verdict is recorded, ``resolve_posture``
therefore does NOT spawn: it warns, emits ``donation_no_verdict`` and
stays undonated. Record the verdict BEFORE launching instead:
``python -m paddle_tpu.programs.donation <store_dir>`` runs the gauntlet
standalone (its child takes the chip first; the parent reads the
fingerprint only after the child has exited) and commits the verdict the
next ProgramStore init will read. ``FLAGS_donation=on|off`` overrides
the probe entirely (``on`` still honors a recorded quarantine).

Test hooks: ``PADDLE_DONATION_PROBE_MODE`` = ``ok`` (skip the donated
trials, report safe) | ``garbage`` (corrupt one probe output — the
simulated corrupting runtime) | ``segv`` (the probe child kills itself
with SIGSEGV). Production leaves it unset.
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time
import warnings
from typing import Any, Dict, Optional

from .. import flags as _flags
from .. import observability as _obs
from ..analysis.runtime import concurrency as _concurrency

_flags.register_flag('FLAGS_donation', 'auto')          # auto | on | off
_flags.register_flag('FLAGS_donation_probe_runs', 8)
_flags.register_flag('FLAGS_donation_probe_timeout', 180.0)
_flags.register_flag('FLAGS_donation_sentinel', 8)      # guarded calls

_VERDICT_VERSION = 1

#: fingerprint-token -> verdict dict; one probe per process per runtime
#: (test helpers reset this via `clear_cache()`)
_PROC_VERDICTS: Dict[str, Dict[str, Any]] = {}
_probe_lock = _concurrency.Lock('donation._probe_lock')


def clear_cache():
    """Drop the process-level verdict cache (tests re-probing)."""
    _PROC_VERDICTS.clear()


def fingerprint_token(fingerprint: Dict[str, Any]) -> str:
    """Stable short token for one backend fingerprint — the key the
    verdict manifest is recorded under."""
    blob = json.dumps(fingerprint, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# the subprocess probe
# ---------------------------------------------------------------------------

# The probe child reproduces the store-served path byte for byte:
# export a donated train-step-shaped program, serialize, deserialize,
# re-apply donation on the wrapper jit, AOT-compile, and drive a chain
# of donated steps per trial — comparing bitwise against the SAME
# exported module compiled undonated. Only jax is imported (the probe
# targets the compiler/runtime boundary, not this framework).
_PROBE_SRC = r'''
import json, os, signal, sys
mode = os.environ.get('PADDLE_DONATION_PROBE_MODE', '')
runs = int(os.environ.get('PADDLE_DONATION_PROBE_RUNS', '8'))
chain = int(os.environ.get('PADDLE_DONATION_PROBE_CHAIN', '6'))
if mode == 'ok':
    print(json.dumps({'ok': True, 'runs': 0, 'detail': 'forced ok'}))
    sys.exit(0)
import numpy as np
import jax
import jax.numpy as jnp
from jax import export as _jex


def step(state, x):
    w, m = state['w'], state['m']
    g = jnp.tanh(x @ w)
    gw = x.T @ g / x.shape[0]
    m2 = 0.9 * m + 0.1 * gw
    w2 = w - 0.05 * m2
    return {'w': w2, 'm': m2}


def init():
    k = jax.random.PRNGKey(0)
    w = jax.random.normal(k, (48, 48), jnp.float32)
    return {'w': w, 'm': jnp.zeros_like(w)}


x = jnp.asarray(np.random.RandomState(1).standard_normal(
    (8, 48)).astype('float32'))
abstract = jax.tree_util.tree_map(
    lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), (init(), x))
plats = tuple(sorted({'cpu', jax.default_backend()}))
exported = _jex.export(jax.jit(step, donate_argnums=(0,)),
                       platforms=plats)(*abstract)
payload = exported.serialize()
de = _jex.deserialize(bytearray(payload))
ref_fn = jax.jit(de.call).lower(*abstract).compile()
don_fn = jax.jit(de.call, donate_argnums=(0,)).lower(*abstract).compile()

state = init()
for _ in range(chain):
    state = ref_fn(state, x)
ref = {k: np.asarray(v) for k, v in state.items()}

if mode == 'segv':
    os.kill(os.getpid(), signal.SIGSEGV)

ok, detail = True, ''
for trial in range(runs):
    state = init()
    for _ in range(chain):
        state = don_fn(state, x)
    got = {k: np.asarray(v) for k, v in state.items()}
    if mode == 'garbage' and trial == runs // 2:
        got['w'] = got['w'].copy()
        got['w'].flat[0] = np.nan
    for k in ref:
        if not np.isfinite(got[k]).all():
            ok, detail = False, f'non-finite output {k!r} on trial {trial}'
            break
        if got[k].tobytes() != ref[k].tobytes():
            ok, detail = False, (
                f'donated output {k!r} diverged from the undonated '
                f'reference on trial {trial}')
            break
    if not ok:
        break
print(json.dumps({'ok': ok, 'runs': runs, 'detail': detail}))
'''


def run_probe(runs: Optional[int] = None,
              timeout: Optional[float] = None) -> Dict[str, Any]:
    """Run the subprocess gauntlet once; returns a verdict dict
    (``verdict`` is 'safe' or 'corrupting' — never raises). The child
    crashing (segfault included) or hanging is itself the corrupting
    classification: a probe that cannot complete cleanly is not a
    runtime to donate on."""
    runs = int(runs if runs is not None
               else _flags.flag('FLAGS_donation_probe_runs'))
    timeout = float(timeout if timeout is not None
                    else _flags.flag('FLAGS_donation_probe_timeout'))
    env = dict(os.environ)
    env['PADDLE_DONATION_PROBE_RUNS'] = str(runs)
    t0 = time.perf_counter()
    verdict: Dict[str, Any] = {
        'version': _VERDICT_VERSION, 'runs': runs,
        'mode': env.get('PADDLE_DONATION_PROBE_MODE', ''),
        'probed_at': time.time(),
    }
    try:
        proc = subprocess.run([sys.executable, '-c', _PROBE_SRC],
                              capture_output=True, text=True,
                              timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        verdict.update(verdict='corrupting',
                       reason=f'probe timed out after {timeout}s')
        verdict['seconds'] = round(time.perf_counter() - t0, 3)
        return verdict
    except Exception as exc:
        verdict.update(verdict='corrupting',
                       reason=f'probe could not launch: '
                              f'{type(exc).__name__}: {exc}')
        return verdict
    verdict['seconds'] = round(time.perf_counter() - t0, 3)
    if proc.returncode != 0:
        sig = -proc.returncode if proc.returncode < 0 else None
        verdict.update(
            verdict='corrupting',
            reason=(f'probe died with signal {sig}' if sig
                    else f'probe exited {proc.returncode}'),
            stderr_tail=proc.stderr[-500:])
        return verdict
    line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() \
        else ''
    try:
        result = json.loads(line)
    except Exception:  # paddle-lint: disable=swallowed-exception -- unparseable probe output IS the corrupting classification recorded in the returned verdict
        verdict.update(verdict='corrupting',
                       reason='probe produced no parseable verdict')
        return verdict
    if result.get('ok'):
        verdict.update(verdict='safe', reason=result.get('detail', ''))
    else:
        verdict.update(verdict='corrupting',
                       reason=result.get('detail', 'output mismatch'))
    return verdict


# ---------------------------------------------------------------------------
# verdict persistence (manifest-recorded, per backend fingerprint)
# ---------------------------------------------------------------------------

def _verdict_path(directory: str, token: str) -> str:
    return os.path.join(directory, f'donation.{token}.json')


def load_verdict(directory: Optional[str],
                 token: str) -> Optional[Dict[str, Any]]:
    """Read the recorded verdict for this fingerprint, or None. An
    unreadable/garbage manifest is treated as absent (re-probe), never
    an exception — the store's poisoned-cache contract."""
    if not directory:
        return None
    path = _verdict_path(directory, token)
    try:
        with open(path) as f:
            data = json.load(f)
    except Exception:  # paddle-lint: disable=swallowed-exception -- unreadable verdict manifest reads as absent: the caller re-probes, the store's poisoned-cache contract
        return None
    if data.get('version') != _VERDICT_VERSION \
            or data.get('verdict') not in ('safe', 'corrupting',
                                           'quarantined'):
        return None
    return data


def record_verdict(directory: Optional[str], token: str,
                   verdict: Dict[str, Any]):
    """Atomically commit the verdict manifest (tmp + rename, like every
    other store artifact). Failures are survivable: the posture still
    holds in the process cache; only re-init re-probes."""
    if not directory:
        return
    try:
        os.makedirs(directory, exist_ok=True)
        path = _verdict_path(directory, token)
        tmp = f'{path}.{os.getpid()}.tmp'
        with open(tmp, 'w') as f:
            json.dump(verdict, f, indent=1, default=str)
        os.replace(tmp, path)
    except Exception:
        _obs.count_suppressed('donation.record_verdict')


def _posture_gauge(value: float):
    if _obs.enabled():
        _obs.get_registry().gauge(
            'paddle_donation_posture',
            'store-served donation posture: 1 enabled, 0 disabled, '
            '-1 quarantined').set(value)


def resolve_posture(directory: Optional[str],
                    fingerprint: Dict[str, Any]) -> Dict[str, Any]:
    """The gauntlet's decision procedure, run at ProgramStore init /
    configure / fingerprint refresh. Returns
    ``{enabled, posture, verdict, reason, source, token}``:

    - ``FLAGS_donation='off'``: donation stays off, no probe (the PR-8
      posture, and what tier-1 pins for determinism).
    - ``'on'``: enabled without probing (operator override) — unless a
      QUARANTINE was recorded for this fingerprint, which always wins.
    - ``'auto'``: recorded verdict (store manifest, then process cache)
      decides; with a persistent directory and no verdict, the
      subprocess probe runs NOW and its verdict is recorded. Without a
      directory nothing is store-served, so no probe runs and donation
      stays off.
    """
    token = fingerprint_token(fingerprint)
    mode = str(_flags.flag('FLAGS_donation') or 'auto').lower()
    out: Dict[str, Any] = {'enabled': False, 'posture': 'off',
                           'verdict': None, 'reason': '', 'source': 'flag',
                           'token': token}
    recorded = load_verdict(directory, token) or _PROC_VERDICTS.get(token)
    if recorded is not None and recorded.get('verdict') == 'quarantined':
        # a quarantine outlives flag overrides: a sentinel caught real
        # corruption on THIS runtime; only wiping the verdict file (or a
        # fingerprint change) re-arms donation
        out.update(posture='quarantined', verdict='quarantined',
                   reason=recorded.get('reason', ''), source='recorded')
        _posture_gauge(-1.0)
        return out
    if mode == 'off':
        out['reason'] = 'FLAGS_donation=off'
        _posture_gauge(0.0)
        return out
    if mode == 'on':
        out.update(enabled=True, posture='on', verdict='forced',
                   reason='FLAGS_donation=on')
        _obs.emit('donation_enabled', token=token, forced=True,
                  sentinel=sentinel_budget())
        _posture_gauge(1.0)
        return out
    # auto: probe-verified only
    if recorded is None:
        if not directory:
            out['reason'] = 'no persistent store (nothing store-served)'
            _posture_gauge(0.0)
            return out
        if fingerprint.get('backend') == 'tpu':
            # this process holds the chip and a chip has one client: a
            # probe child could never attach (see the module docstring)
            out.update(source='no_verdict', reason=(
                f'no verdict — run `python -m paddle_tpu.programs.'
                f'donation {directory}` first'))
            warnings.warn(
                f'donation stays OFF for store-served programs: '
                f'{out["reason"]} (the probe needs the chip this '
                f'process holds)', RuntimeWarning, stacklevel=2)
            _obs.emit('donation_no_verdict', token=token,
                      directory=directory)
            _posture_gauge(0.0)
            return out
        with _probe_lock:
            recorded = load_verdict(directory, token) \
                or _PROC_VERDICTS.get(token)
            if recorded is None:
                with _obs.span('donation.probe'):
                    recorded = run_probe()
                recorded['fingerprint'] = dict(fingerprint)
                _PROC_VERDICTS[token] = recorded
                record_verdict(directory, token, recorded)
                if _obs.enabled():
                    _obs.get_registry().counter(
                        'paddle_donation_probes_total',
                        'donation gauntlet probes by verdict',
                        ('verdict',)).labels(
                            verdict=recorded['verdict']).inc()
                if recorded['verdict'] == 'safe':
                    _obs.emit('donation_probe_ok',
                              runs=recorded.get('runs', 0),
                              seconds=recorded.get('seconds', 0.0))
                else:
                    _obs.emit('donation_probe_failed',
                              reason=recorded.get('reason', ''),
                              seconds=recorded.get('seconds', 0.0))
    else:
        _PROC_VERDICTS.setdefault(token, recorded)
    out.update(verdict=recorded['verdict'],
               reason=recorded.get('reason', ''), source='recorded')
    if recorded['verdict'] == 'safe':
        out.update(enabled=True, posture='on')
        _obs.emit('donation_enabled', token=token,
                  sentinel=sentinel_budget())
        _posture_gauge(1.0)
    else:
        _posture_gauge(0.0)
    return out


def quarantine(directory: Optional[str], fingerprint: Dict[str, Any],
               reason: str) -> Dict[str, Any]:
    """Record that donation CORRUPTED on this runtime (a tripped
    sentinel): the verdict manifest flips to 'quarantined' — which every
    later resolve, flag overrides included, honors — and the event that
    triggers a flight bundle fires. Returns the recorded verdict."""
    token = fingerprint_token(fingerprint)
    verdict = {'version': _VERDICT_VERSION, 'verdict': 'quarantined',
               'reason': str(reason), 'quarantined_at': time.time(),
               'fingerprint': dict(fingerprint)}
    _PROC_VERDICTS[token] = verdict
    record_verdict(directory, token, verdict)
    _obs.emit('donation_quarantined', reason=str(reason), token=token)
    if _obs.enabled():
        _obs.get_registry().counter(
            'paddle_donation_quarantines_total',
            'donation quarantines (sentinel trips)').inc()
    _posture_gauge(-1.0)
    return verdict


# ---------------------------------------------------------------------------
# corruption sentinels
# ---------------------------------------------------------------------------

def sentinel_budget() -> int:
    """Post-enablement invocations of each donated store-served program
    guarded by an output sentinel."""
    try:
        return max(0, int(_flags.flag('FLAGS_donation_sentinel')))
    except Exception:  # paddle-lint: disable=swallowed-exception -- an unparseable flag degrades to the default budget; guarding MORE calls is the safe direction
        return 8


def snapshot_args(args):
    """Device-copy every jax array leaf so the donated call consumes the
    COPIES — the originals stay valid for the undonated re-run a tripped
    sentinel needs. Only used inside the K-call sentinel window, where
    the copy is exactly what the undonated posture paid every call."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda v: jnp.array(v) if isinstance(v, jax.Array) else v, args)


def outputs_ok(out) -> bool:
    """Cheap corruption sentinel over one call's outputs: every
    floating-point leaf must be finite (the device computes the
    reduction; only one scalar per leaf crosses to host). Heap
    corruption manifesting as garbage floats trips this; bitwise
    output divergence is what the PROBE chain catches up front."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    try:
        for leaf in jax.tree_util.tree_leaves(out):
            dt = getattr(leaf, 'dtype', None)
            if dt is None or not jnp.issubdtype(dt, jnp.floating):
                continue
            if not bool(np.asarray(jnp.isfinite(leaf).all())):
                return False
    except Exception:
        # a sentinel that cannot even read the outputs is a trip: the
        # call must fall back to the undonated recompile
        _obs.count_suppressed('donation.sentinel_read')
        return False
    return True


def main(argv=None):
    """``python -m paddle_tpu.programs.donation <store_dir>`` — run the
    gauntlet standalone and record the verdict manifest the next
    ProgramStore init will read (the single-client-device runbook)."""
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ('-h', '--help'):
        print(__doc__.split('\n\n')[0])
        print('\nusage: python -m paddle_tpu.programs.donation '
              '<store_dir> [runs]')
        return 0
    directory = argv[0]
    runs = int(argv[1]) if len(argv) > 1 else None
    # probe FIRST: reading the fingerprint initializes this process's
    # backend, and on a one-client chip the child could then not attach
    verdict = run_probe(runs=runs)
    from .store import backend_fingerprint
    fp = backend_fingerprint()
    token = fingerprint_token(fp)
    verdict['fingerprint'] = fp
    record_verdict(directory, token, verdict)
    print(json.dumps({'token': token, **verdict}, indent=1, default=str))
    return 0 if verdict['verdict'] == 'safe' else 1


if __name__ == '__main__':   # pragma: no cover - exercised via -m
    sys.exit(main())
