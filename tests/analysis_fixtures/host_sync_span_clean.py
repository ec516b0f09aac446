# Clean twin: span selection and lazy growth done right — buckets
# come from host-tracked request state (prompt/token list lengths plus
# the in-flight count), headroom from the host numpy block table; the
# device is never consulted. Never imported.


class InferenceEngine:
    def _slot_rows(self, req):
        return (len(req.prompt) + len(req.tokens)
                + self._inflight_tokens)

    def _round_slots(self, width):
        rungs = {}
        for slot, req in self.slot_req.items():
            rows = self._slot_rows(req)
            if self._ensure_headroom(slot, req, rows + width):
                rungs[slot] = self._span_for(rows)
        span = max(rungs.values(), default=0)
        return (span, list(rungs),
                sum(1 for r in rungs.values() if r < span))

    def _ensure_headroom(self, slot, req, need_rows):
        row = self.block_table[slot]
        have = len(row[row < self.n_kv_blocks])
        return have * self.kv_block >= need_rows
