"""Job kind ``serve_hybrid``: ``serve_arch`` (the paged server under an
open loop, a configuration the file names, the regret check, the
prefix hit rate) for a decoder whose layers are not all attention.

Its one addition: the four stages a state-space layer names in place
of ``qkv`` / ``kv_write`` / ``kv_read`` / ``attention`` / ``attn_out``
(docs/guide/observability.md, "Stage names") join
``program_trace.SCOPES`` for this process, the way ``serve_arch.py``
adds its own three and for its reason: neither file may be edited by
the PR that brings the names, and without them every operation of nine
layers in ten would count as unscoped. (A ``benchmark`` PR should move
all seven names into ``program_trace.SCOPES``: PERF.md, section 7.)
"""
from benchmark import harness, program_trace

_arch = harness.load_module("jobs", "serve_arch.py")

SSM_SCOPES = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out")
program_trace.SCOPES = tuple(
    dict.fromkeys(program_trace.SCOPES + SSM_SCOPES)
)

run = _arch.run
