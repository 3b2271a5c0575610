"""`RepoManager.converge_async` yields by TIME, not by key count: a fold
that is over within `CONVERGE_RUN_S` runs through under the repo lock
without a yield (so no client command ever meets the lock held by it),
and one that runs longer yields after the slice that crossed the budget
(liveness traffic still interleaves with a long sync-dump fold)."""

import asyncio

import jylis_tpu  # noqa: F401
from jylis_tpu.models.manager import RepoManager


class _Repo:
    """Counts converged keys; a fold costs the fake clock ``cost`` a key."""

    def __init__(self, clock, cost):
        self.clock, self.cost, self.keys = clock, cost, 0

    def converge(self, key, delta):
        self.keys += 1
        self.clock.t += self.cost

    def deltas_size(self):
        return 0


class _Clock:
    t = 0.0

    def __call__(self):
        return self.t


def _fold(cost_per_key: float, keys: int) -> tuple[int, int, list[bool]]:
    """(keys converged, turns another task got while the fold ran, whether
    it ever saw the lock free while the fold was unfinished)."""
    clock = _Clock()
    repo = _Repo(clock, cost_per_key)
    mgr = RepoManager("T", repo, None, clock=clock)
    turns, saw_free = 0, []

    async def bystander(done: asyncio.Event):
        nonlocal turns
        while not done.is_set():
            turns += 1
            saw_free.append(not mgr.busy())
            await asyncio.sleep(0)

    async def main():
        done = asyncio.Event()
        task = asyncio.ensure_future(bystander(done))
        await asyncio.sleep(0)  # the bystander's first turn, before the fold
        before = turns
        await mgr.converge_async([(b"k%d" % i, i) for i in range(keys)])
        during = turns - before
        done.set()
        await task
        return during

    during = asyncio.run(main())
    return repo.keys, during, saw_free[1:-1]


def test_a_short_fold_runs_through_without_a_yield():
    slices = 4
    keys = slices * RepoManager.CONVERGE_SLICE
    cost = RepoManager.CONVERGE_RUN_S / keys / 2  # the whole batch: half the budget
    converged, turns, saw_free = _fold(cost, keys)
    assert converged == keys
    assert turns == 0, "nobody may run, so nobody can meet the lock held"
    assert not saw_free


def test_a_long_fold_yields_after_each_slice_over_the_budget_and_keeps_the_lock():
    slices = 5
    keys = slices * RepoManager.CONVERGE_SLICE
    cost = 1.01 * RepoManager.CONVERGE_RUN_S / RepoManager.CONVERGE_SLICE  # one slice: the budget
    converged, turns, saw_free = _fold(cost, keys)
    assert converged == keys
    assert turns == slices - 1, "a yield between slices, none after the last"
    assert not any(saw_free), "the lock stays held across the yields"


def test_the_budget_counts_from_the_last_yield():
    keys = 6 * RepoManager.CONVERGE_SLICE
    cost = 1.01 * RepoManager.CONVERGE_RUN_S / RepoManager.CONVERGE_SLICE / 2  # two slices: the budget
    converged, turns, _ = _fold(cost, keys)
    assert converged == keys and turns == 2  # after slices 2 and 4; none after the 6th
