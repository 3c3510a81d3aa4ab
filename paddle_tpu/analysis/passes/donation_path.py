"""donation-path: raw `donate_argnums` outside the gauntlet-gated store.

PR 8 established that re-applying donation to store-served (exported →
deserialized) executables intermittently heap-corrupted on the jaxlib
of that time (0.4.36); ISSUE 13's donation gauntlet therefore made the
ProgramStore
the single donation owner: callers declare `donate_argnums` to
`wrap_jit`, the DIRECT compile path donates as declared (the safe
case), and the export path re-applies donation only on a probe-safe
verdict, sentinel-guarded, quarantinable.

A raw `donate_argnums=`/`donate_argnames=` keyword on `jax.jit` (or any
other call) bypasses all of that: the donation is baked into the jitted
object where the gauntlet can neither withhold it on a corrupting
runtime nor quarantine it after a sentinel trip. This pass flags every
such keyword outside the store's own modules. The two legitimate
direct-only sites that predate the store (the offload update kernels,
the fleet DistTrainStep) carry inline suppressions with their reasons —
new sites must route through `wrap_jit(..., donate_argnums=...)`.
"""
from __future__ import annotations

import ast
from typing import List

from ..core import AnalysisPass, Finding, SourceFile, register_pass
from . import _util

#: the donation owner itself: applying/recording donate_argnums here IS
#: the gated path
ALLOWED_FILES = frozenset((
    'paddle_tpu/programs/store.py',
    'paddle_tpu/programs/donation.py',
))

#: calls where the keyword is the DECLARATION to the gauntlet, not a
#: bypass of it
GATED_CALLS = frozenset(('wrap_jit',))

DONATE_KEYWORDS = ('donate_argnums', 'donate_argnames')


@register_pass
class DonationPathPass(AnalysisPass):
    name = 'donation-path'
    description = ('raw donate_argnums/donate_argnames outside the '
                   'gauntlet-gated ProgramStore path: donation baked '
                   'into a jit bypasses the probe verdict, the '
                   'corruption sentinels, and quarantine')

    def visit_file(self, sf: SourceFile) -> List[Finding]:
        if sf.rel in ALLOWED_FILES:
            return []
        findings: List[Finding] = []
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            kw = next((k for k in node.keywords
                       if k.arg in DONATE_KEYWORDS), None)
            if kw is None:
                continue
            # gated spelling: the keyword on a wrap_jit(...) call is the
            # declaration to the store, however the receiver is spelled
            # (`store.wrap_jit`, `get_store().wrap_jit`, bare wrap_jit)
            if isinstance(node.func, ast.Attribute):
                seg = node.func.attr
            else:
                seg = _util.last_segment(_util.call_name(node))
            if seg in GATED_CALLS:
                continue
            findings.append(self.finding(
                sf, node,
                f'raw `{kw.arg}` on `{seg or "<call>"}` bypasses the '
                f'donation gauntlet — route it through '
                f'`ProgramStore.wrap_jit(..., donate_argnums=...)` so '
                f'the probe verdict, corruption sentinels, and '
                f'quarantine govern it (whether store-served donated '
                f'executables corrupt on the installed runtime is the '
                f'gauntlet\'s verdict, not the call site\'s)'))
        return findings
