"""Closed loop: one client per ring slot, each resubmitting the moment its
result arrives. Client k's j-th request goes to the j-th tenant of its own
seeded permutation of the tenants, cycled, so every tenant is served alike."""
from __future__ import annotations


class Process:
    def __init__(self, mix, rng, seconds, n_slots, n_tenants):
        self._order = [rng.permutation(n_tenants) for _ in range(n_slots)]
        self._sent = [0] * n_slots

    def _next(self, client: int) -> int:
        order = self._order[client]
        j = self._sent[client]
        self._sent[client] = j + 1
        return int(order[j % len(order)])

    def admit_sizes(self) -> list[int]:
        return [len(self._order)]

    def start(self) -> list[tuple[float, int, int]]:
        return [(0.0, self._next(k), k) for k in range(len(self._order))]

    def on_done(self, client: int, now: float) -> list[tuple[float, int, int]]:
        return [(now, self._next(client), client)]
