"""Serving engines: compiled generate == step-by-step decode; EOS freezing;
mixed-prompt-length compile cache; continuous batching == static generates."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import get_model, init_params
from repro.serving import ContinuousEngine, Engine, Scheduler, ServeConfig

KEY = jax.random.PRNGKey(0)


def _manual_greedy(model, params, prompts, new):
    """Reference decode: prefill + explicit per-step decode_fn calls."""
    S = prompts.shape[1]
    logits, cache = jax.jit(functools.partial(model.prefill_fn, pad_to=S + new + 1))(
        params, {"tokens": prompts}
    )
    cur = jnp.argmax(logits, -1).astype(jnp.int32)
    out = []
    for i in range(new):
        out.append(np.asarray(cur))
        logits, cache = jax.jit(model.decode_fn)(params, cache, cur, jnp.int32(S + i))
        cur = jnp.argmax(logits, -1).astype(jnp.int32)
    return np.stack(out, 1)


def test_engine_greedy_matches_manual_decode():
    cfg = configs.get_smoke("tinyllama_1_1b")
    model = get_model(cfg)
    params = init_params(jax.random.PRNGKey(1), model.specs)
    B, S, NEW = 2, 32, 6
    prompts = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    eng = Engine(model, ServeConfig(max_new=NEW, temperature=0.0))
    toks = np.asarray(eng.generate(params, {"tokens": prompts}))
    np.testing.assert_array_equal(toks, _manual_greedy(model, params, prompts, NEW))


def test_engine_mixed_prompt_lengths_use_correct_positions():
    """Regression: the compiled generate used to be cached keyed on nothing, so
    a second call with a different prompt length decoded at the first call's
    positions. Both lengths must match the manual reference."""
    cfg = configs.get_smoke("smollm_360m")
    model = get_model(cfg)
    params = init_params(jax.random.PRNGKey(1), model.specs)
    NEW = 4
    eng = Engine(model, ServeConfig(max_new=NEW, temperature=0.0))
    for S in (16, 24):
        prompts = jax.random.randint(jax.random.PRNGKey(S), (2, S), 0, cfg.vocab)
        toks = np.asarray(eng.generate(params, {"tokens": prompts}))
        np.testing.assert_array_equal(
            toks, _manual_greedy(model, params, prompts, NEW)
        )
    assert len(eng._gen) == 2  # one compiled program per prompt shape


def test_engine_eos_freezes_sequences():
    cfg = configs.get_smoke("smollm_360m")
    model = get_model(cfg)
    params = init_params(jax.random.PRNGKey(1), model.specs)
    B, S = 2, 16
    prompts = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    # pick the first greedily generated token as "EOS" so it triggers immediately
    eng0 = Engine(model, ServeConfig(max_new=4, temperature=0.0))
    first = int(np.asarray(eng0.generate(params, {"tokens": prompts}))[0, 0])
    eng = Engine(model, ServeConfig(max_new=6, temperature=0.0, eos_id=first))
    toks = np.asarray(eng.generate(params, {"tokens": prompts}))
    row = toks[0]
    hit = np.where(row == first)[0]
    assert hit.size > 0
    assert (row[hit[0]:] == first).all()  # frozen after EOS


def test_continuous_matches_static_on_mixed_length_trace():
    """A mixed-length request trace through the scheduler must yield greedy
    outputs token-identical to per-request static generates, with one prefill
    compile per length bucket and one shared step program."""
    cfg = configs.get_smoke("smollm_360m")
    model = get_model(cfg)
    params = init_params(jax.random.PRNGKey(1), model.specs)
    NEW = 4
    scfg = ServeConfig(max_new=NEW, temperature=0.0)
    lengths = [8, 12, 8, 16, 12, 8]
    prompts = [
        jax.random.randint(jax.random.PRNGKey(10 + i), (1, L), 0, cfg.vocab)
        for i, L in enumerate(lengths)
    ]
    static = Engine(model, scfg)
    want = [np.asarray(static.generate(params, {"tokens": p}))[0] for p in prompts]

    eng = ContinuousEngine(model, scfg, num_slots=2, max_prompt_len=16)
    sched = Scheduler(eng, params)
    rids = [sched.submit(p[0]) for p in prompts]
    results = sched.run(timeout=600)
    assert len(results) == len(prompts)
    for rid, w in zip(rids, want):
        got = sched.poll(rid)
        assert got is not None and got.finish_reason == "length"
        np.testing.assert_array_equal(np.asarray(got.tokens), w)
    # 3 length buckets -> 3 prefill compiles; admission/eviction never recompiles
    assert len(eng._prefill_sigs) == 3
    # 6 requests x 3 steps each on 2 slots => slots were reused mid-stream
    assert sched.steps < len(prompts) * (NEW - 1)


def test_chunked_prefill_matches_static():
    """Long prompts admitted chunk-by-chunk (fixed 8-token chunks interleaved
    with decode steps) must yield greedy outputs token-identical to the static
    per-request generates; full chunks share compiled programs across prompt
    lengths (only remainder chunks are per-length)."""
    cfg = configs.get_smoke("smollm_360m")
    model = get_model(cfg)
    assert model.prefill_chunk_fn is not None  # dense decoder exposes chunking
    params = init_params(jax.random.PRNGKey(1), model.specs)
    NEW = 4
    scfg = ServeConfig(max_new=NEW, temperature=0.0)
    lengths = [8, 20, 26, 8, 20]
    prompts = [
        jax.random.randint(jax.random.PRNGKey(10 + i), (1, L), 0, cfg.vocab)
        for i, L in enumerate(lengths)
    ]
    static = Engine(model, scfg)
    want = [np.asarray(static.generate(params, {"tokens": p}))[0] for p in prompts]
    eng = ContinuousEngine(model, scfg, num_slots=2, max_prompt_len=26,
                           prefill_chunk=8)
    sched = Scheduler(eng, params)
    rids = [sched.submit(p[0]) for p in prompts]
    results = sched.run(timeout=600)
    assert len(results) == len(prompts)
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(np.asarray(sched.poll(rid).tokens), w)
    # chunking actually ran: len-8 prompts take the whole-prefill path (one
    # sig), longer prompts chunk — full chunks (0,8),(8,8),(16,8) shared,
    # remainders (16,4),(24,2) per-length
    assert len(eng._prefill_sigs) == 1
    assert sorted(eng._chunk_sigs) == [(0, 8), (8, 8), (16, 4), (16, 8), (24, 2)]


def test_admission_is_age_fair_across_buckets():
    """Regression: the old policy admitted from the oldest request's bucket
    until EMPTY, so under sustained long-prompt load a short prompt that
    arrived in between was starved. Age-fair admission re-picks the globally
    oldest pending request for each free slot."""
    cfg = configs.get_smoke("smollm_360m")
    model = get_model(cfg)
    params = init_params(jax.random.PRNGKey(1), model.specs)
    scfg = ServeConfig(max_new=3, temperature=0.0)
    eng = ContinuousEngine(model, scfg, num_slots=2, max_prompt_len=16)
    tick = iter(range(10_000))
    sched = Scheduler(eng, params, clock=lambda: float(next(tick)))
    long_p = [jax.random.randint(jax.random.PRNGKey(30 + i), (1, 16), 0, cfg.vocab)
              for i in range(3)]
    short_p = jax.random.randint(jax.random.PRNGKey(40), (1, 8), 0, cfg.vocab)
    r_long0 = sched.submit(long_p[0][0])   # t=0
    r_short = sched.submit(short_p[0])     # t=1
    r_long1 = sched.submit(long_p[1][0])   # t=2
    r_long2 = sched.submit(long_p[2][0])   # t=3
    sched.run()
    # the 2 slots must admit the two globally oldest first: long0 then short —
    # NOT long0+long1 (the old drain-the-oldest-bucket policy)
    t_admit = {r: sched.poll(r).t_admit for r in (r_long0, r_short, r_long1, r_long2)}
    assert t_admit[r_long0] < t_admit[r_short] < t_admit[r_long1] < t_admit[r_long2]


def test_continuous_eos_evicts_and_refills_slot():
    """EOS finishes a request early; the freed slot admits the next pending
    request while the other slot keeps decoding."""
    cfg = configs.get_smoke("smollm_360m")
    model = get_model(cfg)
    params = init_params(jax.random.PRNGKey(1), model.specs)
    prompts = [
        jax.random.randint(jax.random.PRNGKey(20 + i), (1, 8), 0, cfg.vocab)
        for i in range(3)
    ]
    # choose request 0's second greedy token as EOS so it finishes mid-decode
    probe = Engine(model, ServeConfig(max_new=2, temperature=0.0))
    eos = int(np.asarray(probe.generate(params, {"tokens": prompts[0]}))[0, 1])

    scfg = ServeConfig(max_new=6, temperature=0.0, eos_id=eos)
    eng = ContinuousEngine(model, scfg, num_slots=1, max_prompt_len=8)
    sched = Scheduler(eng, params)
    rids = [sched.submit(p[0]) for p in prompts]
    results = sched.run(timeout=600)
    assert len(results) == 3
    first = sched.poll(rids[0])
    assert first.finish_reason == "eos"
    assert first.tokens[-1] == eos and len(first.tokens) <= 6
    # every request matches its own static generate (trimmed after first EOS)
    static = Engine(model, scfg)
    for rid, p in zip(rids, prompts):
        got = sched.poll(rid)
        w = np.asarray(static.generate(params, {"tokens": p}))[0]
        np.testing.assert_array_equal(np.asarray(got.tokens), w[: len(got.tokens)])
        if got.finish_reason == "eos":  # static freezes to EOS past the finish
            assert (w[len(got.tokens):] == eos).all() if len(got.tokens) < 6 else True


def test_slot_leak_guard_evicts_requeues_and_drains():
    """Regression: a request that never finishes (decode loop that never hits
    EOS, or a backend bug) used to pin its slot forever — run() spun until the
    wall-clock timeout raised with the slot still held. With max_slot_steps
    the slot is force-evicted (freed + engine.on_evict), the request requeued
    at the head of its bucket up to max_requeues times, then failed with an
    'evicted' completion — the queue always drains."""
    import itertools
    import types

    from repro.serving import Completion, SlotScheduler
    from repro.serving.slotring import SlotRingEngine, slot_update

    class NeverEngine(SlotRingEngine):
        """Slots never finish on their own; records forced evictions."""

        def __init__(self):
            self.evicted = []
            super().__init__(num_slots=2)

        def init_state(self):
            return {"rid": jnp.zeros((2,), jnp.int32)}

        def _step_impl(self, params, state):
            return state, state["rid"]

        def _admit_impl(self, state, rid, slot):
            return slot_update(state, {"rid": rid}, slot)

        def on_evict(self, slot):
            self.evicted.append(slot)

    class NeverScheduler(SlotScheduler):
        def submit(self):
            rid = self._next_rid
            self._next_rid += 1
            self.buckets[0].append(
                types.SimpleNamespace(rid=rid, t_submit=self.clock()))
            return rid

        def _start_admission(self, req, slot):
            self.state = self.engine._admit_fn(
                self.state, jnp.int32(req.rid), jnp.int32(slot))
            self.running[slot] = (req, self.clock())
            return []

        def _collect(self, emitted):
            return []                  # nothing ever finishes normally

        def _fail_eviction(self, slot, record):
            req, t_admit = record
            return Completion(req.rid, [], "evicted", 0, req.t_submit,
                              t_admit, self.clock())

    def fake_clock(counter=itertools.count()):
        return float(next(counter))

    # ungated: the leak reproduces — run() can only time out
    leaky = NeverScheduler(NeverEngine(), None, fake_clock)
    leaky.submit()
    with pytest.raises(TimeoutError, match="did not drain"):
        leaky.run(timeout=50.0)
    assert 0 in leaky.running and 0 not in leaky.free  # slot still pinned

    # guard rejects a useless deadline
    with pytest.raises(ValueError, match="max_slot_steps"):
        NeverScheduler(NeverEngine(), None, fake_clock, max_slot_steps=0)

    # gated: both requests get evicted, requeued once, evicted again, failed
    eng = NeverEngine()
    sched = NeverScheduler(eng, None, fake_clock,
                           max_slot_steps=3, max_requeues=1)
    rids = [sched.submit(), sched.submit()]
    results = sched.run(timeout=10_000.0)
    assert sorted(results) == sorted(rids)
    assert all(results[r].finish_reason == "evicted" for r in rids)
    assert sched.steps == 6                  # 3 per attempt, 2 attempts
    assert len(eng.evicted) == 4             # 2 slots x 2 attempts
    assert not sched.running and sorted(sched.free) == [0, 1]


def test_scheduler_counts_running_and_computed_slot_steps():
    """``slot_steps`` sums the running slots of every engine step and
    ``computed_slot_steps`` the slots each step computed, so a drain with
    uneven request lengths reads a slot fill below 1."""
    cfg = configs.get_smoke("smollm_360m")
    model = get_model(cfg)
    params = init_params(jax.random.PRNGKey(1), model.specs)
    eng = ContinuousEngine(model, ServeConfig(max_new=4, temperature=0.0),
                           num_slots=2, max_prompt_len=8)
    sched = Scheduler(eng, params)
    running = []
    step = eng.step

    def counted(p, state):
        running.append(len(sched.running))
        return step(p, state)

    eng.step = counted
    for i, new in enumerate((4, 2, 2)):
        sched.submit(jax.random.randint(jax.random.PRNGKey(30 + i), (8,), 0,
                                        cfg.vocab), max_new=new)
    sched.run(timeout=600)
    assert sched.steps == len(running) and min(running) == 1
    assert sched.slot_steps == sum(running)
    assert sched.computed_slot_steps == eng.num_slots * sched.steps
    assert sched.slot_steps < sched.computed_slot_steps
