"""A test-only model module: DPLR-FwFM with its reference scores scaled
by (1 + 1e-5), so a sound run compared through it must come out not
correct (``bench/tests/test_rehearsal.py``)."""
from bench import harness

_base = harness.model_module("dplr_fwfm")
KERNEL_EVENT = _base.KERNEL_EVENT
Layout, check, draw = _base.Layout, _base.check, _base.draw
query_rows, item_rows = _base.query_rows, _base.item_rows
request_flops = _base.request_flops
launch_flops, launch_bytes = _base.launch_flops, _base.launch_bytes


class Reference(_base.Reference):
    def scores(self, queries, items):
        score, absum = super().scores(queries, items)
        return score * (1 + 1e-5), absum
