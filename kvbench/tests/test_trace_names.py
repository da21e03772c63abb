"""The trace readers' patterns against the names the program pins
(``llama.PROGRAM_*``, ``pallas_paged_attention.KERNEL_*``): a reader keyed
on a name the program no longer gives reads nothing, silently."""

import re

import pytest

from kvbench.harness import names
from llmd_kv_cache_tpu.models import llama
from llmd_kv_cache_tpu.ops import pallas_paged_attention as ppa


@pytest.mark.parametrize("metric, program, kernel", [
    ("decode_step_ms_p50", "PROGRAM_DECODE", None),
    ("prefill_step_ms_p50", "PROGRAM_PREFILL", None),
    ("prefill_mfu", "PROGRAM_PREFILL", None),
    ("attn_decode_roofline", "PROGRAM_DECODE", "KERNEL_DECODE"),
])
def test_each_trace_reader_matches_the_constant(metric, program, kernel):
    reader = names.metric(metric)
    # The modules line names an execution jit_<name>(<fingerprint>).
    module = f"jit_{getattr(llama, program)}(1954997301803068618)"
    assert re.search(reader.PROGRAM, module)
    other = {"PROGRAM_DECODE": llama.PROGRAM_PREFILL,
             "PROGRAM_PREFILL": llama.PROGRAM_DECODE}[program]
    assert not re.search(reader.PROGRAM, f"jit_{other}(7)")
    if kernel:
        assert re.search(reader.KERNEL, f"{getattr(ppa, kernel)}.12")
        assert not re.search(reader.KERNEL, f"{ppa.KERNEL_PREFILL}.12")
