"""memquota in plain python: the quota half of a configuration's plain
reference, written from the adapter's documented behaviour (istio 0.5
mixer/adapter/memquota: `quotas[].max_amount`; the Quota API's
`amount`, `best_effort` and `deduplication_id`). Nothing of istio_tpu:
a configuration module takes this file by its path and hands it the
precondition's reference, the rule's match and the instance's
dimensions.

    grant(request, name, amount, best_effort, dedup_id)
        -> granted_amount | None

`None`: the reply must carry no entry (the precondition denied, so the
quota loop never ran). A quota no active rule serves is granted freely
and consumes nothing. Otherwise the instance key is the dimensions'
values; an all-or-nothing ask gets `amount` or 0, a best-effort one
what is left; a `dedup_id` seen before returns its first answer and
consumes nothing. The counter is exact and never expires: a
configuration with a `valid_duration`, or one that re-sends an id past
`min_deduplication_duration`, brings those semantics with the cell
that proves them.

    consumes(request, name) -> whether such an ask counts against a key
    key_of(request), in_use(request) -> the key, what it has consumed
    consume(request, amount)   the same key moved on with no ask
        (run.py: what the window's sends took, by the client's count)
"""
from __future__ import annotations


class MemQuota:
    def __init__(self, name: str, max_amount: int, expected_status,
                 key_of, rule_matches=lambda request: True):
        self.name, self.max_amount = name, max_amount
        self.expected_status, self.key_of = expected_status, key_of
        self.rule_matches = rule_matches
        self.used: dict = {}      # key -> amount
        self.answers: dict = {}   # (dedup id, name) -> granted

    def consumes(self, request: dict, name: str) -> bool:
        return self.expected_status(request) == 0 \
            and name == self.name and bool(self.rule_matches(request))

    def in_use(self, request: dict) -> int:
        return self.used.get(self.key_of(request), 0)

    def consume(self, request: dict, amount: int) -> None:
        key = self.key_of(request)
        self.used[key] = self.used.get(key, 0) + amount

    def grant(self, request: dict, name: str, amount: int,
              best_effort: bool, dedup_id: str):
        if self.expected_status(request) != 0:
            return None
        if not self.consumes(request, name):
            return amount
        if dedup_id and (dedup_id, name) in self.answers:
            return self.answers[dedup_id, name]
        left = self.max_amount - self.in_use(request)
        granted = max(min(amount, left), 0) if best_effort \
            else (amount if left >= amount > 0 else 0)
        if granted:
            self.consume(request, granted)
        if dedup_id:
            self.answers[dedup_id, name] = granted
        return granted
