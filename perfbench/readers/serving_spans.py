"""The serving engine's own spans and counts over the measured window, from
the program's span ring (`program_ring`): records that ended in
[`t_open`, `t_close`]. Nothing where the program records no such span, or
the ring overflowed inside the window.

- `queue_wait_ms`: the q-th percentile of `serving/queue_wait` (submit to
  the start of admission), milliseconds.
- `admit_ms_per_ktok`: wall milliseconds of all `serving/admit` spans over
  the thousands of prompt tokens they admitted; stands beside
  `prefill_ms_per_ktok` (device time only).
- `admit_stall_share`: of the time rows waited for a token, the share spent
  behind a prefill: sum over admissions of duration x `rows` (the decoding
  rows it stalled), over that plus the same sum across decode steps.
- `gather_useful_share`: sum of `pages_live` over sum of `pages_gathered`
  across the decode steps: of the pages every layer's gather touches, the
  share that holds a position some row attends to.
"""

from perfbench import stats
from perfbench.readers import program_ring


def read(run, what, q=None):
    spans = program_ring.ended_between(run, "t_open", "t_close")
    admits = program_ring.named(spans, "serving/admit")
    decodes = program_ring.named(spans, "serving/decode")
    if what == "queue_wait_ms":
        waits = program_ring.named(spans, "serving/queue_wait")
        return stats.percentile([s.duration_ns / 1e6 for s in waits], q)
    if what == "admit_ms_per_ktok":
        tokens = sum(s.args["prompt_tokens"] for s in admits)
        return sum(s.duration_ns for s in admits) / 1e6 / (tokens / 1e3) \
            if tokens else None
    if what == "admit_stall_share":
        stalled = sum(s.duration_ns * s.args["rows"] for s in admits)
        decoding = sum(s.duration_ns * s.args["rows"] for s in decodes)
        return 100.0 * stalled / (stalled + decoding) if decodes else None
    if what == "gather_useful_share":
        gathered = sum(s.args["pages_gathered"] for s in decodes)
        return 100.0 * sum(s.args["pages_live"] for s in decodes) / gathered \
            if gathered else None
    raise ValueError(what)
