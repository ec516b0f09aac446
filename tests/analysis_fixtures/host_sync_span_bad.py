# Golden fixture: seeded host-sync violations on the span-selection /
# lazy-growth path. Span buckets and block headroom must come from
# HOST bookkeeping (request token lists, the numpy block table) —
# peeking at device lengths to pick a bucket would drain the dispatch
# pipeline once per burst. Checked as if it were
# skypilot_tpu/infer/engine.py (the hot-loop scope). Never imported.
import numpy as np


class InferenceEngine:
    def _round_slots(self, width):
        lengths = np.asarray(self.cache["length"])  # expect: host-sync
        rungs = {}
        for slot in self.slot_req:
            rows = int(self.cache["length"][slot])  # expect: host-sync
            rungs[slot] = self._span_for(rows)
        span = max(rungs.values(), default=0)
        return span, list(rungs), lengths

    def _ensure_headroom(self, slot, req, need_rows):
        used = self.cache["length"].item()          # expect: host-sync
        return used < need_rows
