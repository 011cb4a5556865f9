"""Overlapped admission (engine/batcher.py:_prep_loop).

The tentpole contract of the asynchronous device-feed pipeline:

* **Parity** — greedy output is byte-identical with
  ``engine_overlap_admission`` on vs off, across paged/dense caches ×
  speculate on/off, with the prefix cache enabled (so the prep thread's
  match path runs), a JSON-masked slot, and staggered budgets that
  finish slots mid-chunk. Moving admission prep to another thread must
  change WHEN work happens, never WHAT tokens come out.
* **Stress** (slow) — admissions, including chunked-prefill segments,
  arriving MID-decode while deadlines expire under overlap: per-slot
  early release + overlapped prep compose without hung futures, leaked
  slots or leaked pages.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from pilottai_tpu.engine.batcher import ContinuousBatcher, GenRequest
from pilottai_tpu.models.common import init_params
from pilottai_tpu.models.registry import get_model_config
from pilottai_tpu.reliability import DeadlineExceeded

# Staggered budgets -> slots finish mid-chunk at different blocks; one
# slot decodes under the JSON grammar mask; two requests share a prompt
# prefix so the prefix-cache path participates.
REQS = (
    (list(range(3, 11)), 6, False),
    (list(range(3, 11)) + [17, 18], 12, False),   # shares an 8-token prefix
    (list(range(23, 36)), 9, True),
    (list(range(41, 48)), 2, False),
    (list(range(51, 60)), 15, False),
)


def _make_batcher(overlap, *, paged, speculate):
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return ContinuousBatcher(
        cfg, params, n_slots=4, max_seq_len=96, cache_dtype=jnp.float32,
        chunk_size=6, paged=paged, page_size=16, speculate=speculate,
        prefix_cache=2, use_pallas=False, overlap_admission=overlap,
    )


def _run_batch(overlap, *, paged, speculate, reqs=REQS):
    b = _make_batcher(overlap, paged=paged, speculate=speculate)
    # Submit everything BEFORE starting so admission order (and with it
    # grouping/padding) is identical run to run.
    reqs_out = []
    for prompt, mnt, json_mode in reqs:
        req = GenRequest(
            prompt_ids=list(prompt), max_new_tokens=mnt, json_mode=json_mode
        )
        b.submit(req)
        reqs_out.append(req)
    b.start()
    try:
        outs = [r.future.result(timeout=600) for r in reqs_out]
    finally:
        b.stop()
    return outs


@pytest.mark.parametrize(
    "paged,speculate",
    [(False, 0), (False, 2), (True, 0), (True, 2)],
    ids=["dense", "dense-spec", "paged", "paged-spec"],
)
def test_overlap_matches_inline_greedy(paged, speculate):
    inline = _run_batch(False, paged=paged, speculate=speculate)
    overlapped = _run_batch(True, paged=paged, speculate=speculate)
    assert overlapped == inline, (
        f"overlapped admission changed greedy output (paged={paged}, "
        f"speculate={speculate})"
    )
    assert all(len(o) >= 1 for o in inline)  # non-vacuous


def test_engine_stays_serviceable_after_overlap_run():
    """The prep thread shuts down cleanly and a restarted batcher serves
    again — no slot/reservation leak survives a stop()."""
    b = _make_batcher(True, paged=True, speculate=0)
    req = GenRequest(prompt_ids=list(range(5, 15)), max_new_tokens=4)
    b.submit(req)
    b.start()
    assert len(req.future.result(timeout=300)) >= 1
    b.stop()
    assert not b._prep_reserved
    assert all(s is None for s in b._slots)


@pytest.mark.slow
def test_stress_admissions_mid_decode_with_deadlines_and_segments():
    """Admissions (short prompts AND a chunked-prefill long prompt)
    arrive while decode is in flight, some with deadlines that expire
    mid-decode. Pins that per-slot early release (PR 4) and overlapped
    admission compose: every future resolves (tokens or
    DeadlineExceeded), no slot stays occupied, no page leaks beyond the
    prefix index's deliberate pins, and the engine still serves after."""
    cfg = get_model_config("llama-tiny")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    b = ContinuousBatcher(
        cfg, params, n_slots=4, max_seq_len=128, cache_dtype=jnp.float32,
        chunk_size=4, paged=True, page_size=16, num_pages=24,
        prefill_chunk=32, prefix_cache=2, use_pallas=False,
        overlap_admission=True,
    )
    b.start()
    done, expired = 0, 0
    try:
        # Wave 1: keep the device decoding.
        wave1 = [
            GenRequest(prompt_ids=list(range(3 + i, 20 + i)),
                       max_new_tokens=24)
            for i in range(3)
        ]
        for r in wave1:
            b.submit(r)
        time.sleep(0.05)  # mid-decode
        # Wave 2: a long prompt that MUST segment (tail > 2 *
        # prefill_chunk = 64), plus short requests with tight deadlines.
        long_req = GenRequest(
            prompt_ids=list(range(2, 2 + 80)), max_new_tokens=8
        )
        b.submit(long_req)
        # i=0 is born practically expired (the prep thread's backlog
        # sweep must fail it without spending a prefill); the rest race
        # their decode budget.
        deadliners = [
            GenRequest(
                prompt_ids=list(range(60 + i, 75 + i)), max_new_tokens=64,
                deadline=time.monotonic() + (0.001 if i == 0 else 0.1 * i),
            )
            for i in range(4)
        ]
        for r in deadliners:
            b.submit(r)
        for r in wave1 + [long_req] + deadliners:
            try:
                out = r.future.result(timeout=600)
                assert isinstance(out, list)
                done += 1
            except DeadlineExceeded:
                expired += 1
        # Non-vacuous: the full-budget work completed AND at least the
        # born-expired request was failed with DeadlineExceeded.
        assert done >= 4
        assert expired >= 1
        assert len(long_req.future.result()) >= 1
        # Engine still serves after the churn.
        probe = GenRequest(prompt_ids=list(range(9, 21)), max_new_tokens=4)
        b.submit(probe)
        assert len(probe.future.result(timeout=300)) >= 1
        # No slot leaked...
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with b._lock:
                if all(s is None for s in b._slots):
                    break
            time.sleep(0.05)
        with b._lock:
            assert all(s is None for s in b._slots)
        # ...and every page is either free or a deliberate prefix pin.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with b._lock:
                total = b.num_pages - 1
                balanced = (
                    b.alloc.free_pages + b.page_index.pinned_pages == total
                )
            if balanced:
                break
            time.sleep(0.05)
        with b._lock:
            assert (
                b.alloc.free_pages + b.page_index.pinned_pages
                == b.num_pages - 1
            ), "pages leaked to dead slots"
    finally:
        b.stop()


def test_selection_failure_unwinds_committed_admissions():
    """A mid-selection exception (prefix match, eviction, the allocate
    assert) must roll back EVERYTHING the call already committed. The
    keep-alive catches in _prep_loop/_run only log: before the unwind,
    earlier members of the in-progress group kept their _prep_reserved
    entries and page allocations forever while their requests vanished
    from every queue — futures never resolved and the slot pool
    permanently shrank."""
    b = _make_batcher(True, paged=True, speculate=0)  # never started
    reqs = [
        GenRequest(prompt_ids=list(range(3, 11 + i)), max_new_tokens=4)
        for i in range(3)
    ]
    b._backlog.extend(reqs)
    free_before = b.alloc.free_pages
    calls = {"n": 0}
    orig = b._prefix_hit

    def flaky(req):
        calls["n"] += 1
        if calls["n"] == 3:  # two members already committed
            raise RuntimeError("injected prefix-index fault")
        return orig(req)

    b._prefix_hit = flaky
    with pytest.raises(RuntimeError):
        b._select_groups()
    assert not b._prep_reserved, "reservations leaked by failed selection"
    assert b.alloc.free_pages == free_before, "pages leaked"
    assert [r.prompt_ids for r in b._backlog] == [
        r.prompt_ids for r in reqs
    ], "backlog FIFO order not restored"
    # The engine recovers once the fault clears: selection now forms the
    # same admission group it would have originally.
    b._prefix_hit = orig
    groups, seg, _ = b._select_groups()
    assert seg is None
    assert [req for _, g in groups for _, req in g] == reqs


def test_all_expired_prep_skips_dispatch():
    """A _PreparedAdmission can wait in _prepped across a whole
    chunked-prefill segmentation — long past _select_groups' deadline
    sweep. If every member expired meanwhile, the fused prefill is 100%
    dead work: the device thread must fail the group (releasing pages
    and reservations) without spending the dispatch."""
    b = _make_batcher(True, paged=True, speculate=0)  # never started
    req = GenRequest(
        prompt_ids=list(range(3, 11)), max_new_tokens=4,
        deadline=time.monotonic() + 30,
    )
    b._backlog.append(req)
    free_before = b.alloc.free_pages
    groups, seg, epoch = b._select_groups()
    assert groups and seg is None
    prep = b._prepare_prefill(groups[0][1], groups[0][0], epoch=epoch)
    req.deadline = time.monotonic() - 0.001  # expired while queued

    def boom(_prep):
        raise AssertionError("dispatched a fully-expired group")

    b._dispatch_prefill = boom
    b._dispatch_admissions([prep])
    with pytest.raises(DeadlineExceeded):
        req.future.result(timeout=1)
    assert not b._prep_reserved, "reservation leaked on expired drop"
    assert b.alloc.free_pages == free_before, "pages leaked on expired drop"
