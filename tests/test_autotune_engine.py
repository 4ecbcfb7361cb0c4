"""Engine hot-switch contract (cake_tpu/autotune + engine.reconfigure).

The token-identity pins: a greedy stream served ACROSS a live config
switch emits exactly the tokens an uninterrupted run would (f32 KV —
bf16 storage flips greedy near-ties and would test tie-breaks, not the
fold), on the dense AND the paged engine, shared-prefix slots included;
the refcounted page pool is conserved; the int8-pool -> float-pool
direction is gated off with a loud reason; and a pool no in-flight
stream fits refuses the switch instead of dropping anyone. Plus the
300-step random submit/cancel/switch property test and the
/api/v1/autotune API contract.
"""

import random
import time

import pytest

import jax.numpy as jnp

from cake_tpu.serve.errors import SwitchInFlightError

T = 64
PAGE = 16


@pytest.fixture(scope="module")
def params(tiny_config):
    import jax
    from cake_tpu.models.llama.params import init_params
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


def _engine(tiny_config, params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    kw.setdefault("max_slots", 2)
    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        max_seq_len=T,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        # f32 KV to match the f32 params fixture: greedy equality must
        # exercise the hot-switch fold, not bf16 tie-breaks
        cache_dtype=jnp.float32,
        **kw)


def _wait_tokens(handle, n, timeout=120.0):
    t0 = time.perf_counter()
    while (len(handle._req.out_tokens) < n
           and time.perf_counter() - t0 < timeout):
        time.sleep(0.002)
    assert len(handle._req.out_tokens) >= n, "stream never got going"


PROMPT = [5, 9, 2, 7, 5, 3, 11, 4, 6]


def test_dense_switch_token_identity(tiny_config, params):
    with _engine(tiny_config, params) as eng:
        h = eng.submit(PROMPT, max_new_tokens=24)
        assert h.wait(120)
        baseline = list(h._req.out_tokens)
    with _engine(tiny_config, params) as eng:
        h = eng.submit(PROMPT, max_new_tokens=24)
        _wait_tokens(h, 6)
        # slots AND decode_scan move in one switch
        assert eng.reconfigure({"slots": 4, "decode_scan": 3}) is True
        assert h.wait(120)
        assert list(h._req.out_tokens) == baseline
        assert eng.max_slots == 4 and eng._decode_scan == 3
        assert eng.config_epoch == 1
        assert eng.stats.config_switches == 1
        # the trace records the admission epoch + the switch span
        rec = eng.tracer.dump(limit=4)[0]
        assert rec["config_epoch"] == 0
        assert any(s["name"] == "reconfigured" for s in rec["spans"])


def test_dense_to_paged_switch_token_identity(tiny_config, params):
    with _engine(tiny_config, params) as eng:
        h = eng.submit(PROMPT, max_new_tokens=20)
        assert h.wait(120)
        baseline = list(h._req.out_tokens)
    with _engine(tiny_config, params) as eng:
        h = eng.submit(PROMPT, max_new_tokens=20)
        _wait_tokens(h, 5)
        assert eng.reconfigure({"slots": 2, "kv_pages": 16,
                                "kv_page_size": PAGE,
                                "paged_attn": "fold"}) is True
        assert h.wait(120)
        assert list(h._req.out_tokens) == baseline
        assert eng.paged and eng.cache.n_pages == 16
        # the carried stream's pages release on retirement: conserved
        assert eng._pager.free_pages == eng.cache.n_pages


def test_paged_switch_token_identity_with_shared_prefix(tiny_config,
                                                        params):
    prefix = [7] * PAGE
    prompts = [prefix + [5, 3, 9], prefix + [4, 8, 2, 6]]

    def run(switch: bool):
        eng = _engine(tiny_config, params, kv_pages=16,
                      kv_page_size=PAGE, paged_attn="fold")
        with eng:
            eng.register_prefix(prefix)
            hs = [eng.submit(p, max_new_tokens=16) for p in prompts]
            if switch:
                _wait_tokens(hs[0], 4)
                # pool geometry AND slot count move together; the
                # shared-prefix slots are mid-decode when they fold
                assert eng.reconfigure({"slots": 4, "kv_pages": 24,
                                        "kv_page_size": PAGE,
                                        "paged_attn": "fold"}) is True
            assert all(h.wait(120) for h in hs)
            toks = [list(h._req.out_tokens) for h in hs]
            assert eng.stats.prefix_hits >= len(prompts)
            # pool conservation once every stream retired: the only
            # live pages left are the registry's own prefix reference
            # (cleared by a switch — auto-prefix re-registers later)
            registry = sum(len(pages) for (_ids, pages, _x)
                           in eng._prefixes.values() if pages)
            assert (eng._pager.free_pages + registry
                    == eng.cache.n_pages)
            assert registry == (0 if switch else 1)
        return toks

    assert run(switch=True) == run(switch=False)


def test_int8_to_float_switch_gated_loudly(tiny_config, params):
    eng = _engine(tiny_config, params, kv_pages=8, kv_page_size=32,
                  kv_dtype="int8", paged_attn="fold")
    with pytest.raises(ValueError, match="int8-pool -> float-pool"):
        eng.reconfigure({"slots": 2, "kv_pages": 8, "kv_page_size": 32,
                         "paged_attn": "fold"})
    # int8 -> int8 geometry moves stay allowed
    assert eng.reconfigure({"slots": 4, "kv_pages": 8,
                            "kv_page_size": 32, "kv_dtype": "int8",
                            "paged_attn": "fold"}) is True


def test_int4_widening_switches_gated_loudly(tiny_config, params):
    """The int4 rung of the precision lattice at the engine seam: both
    widening directions refuse with the lattice reason; the narrowing
    int8 -> int4 hot switch (the pool-pressure escalation's move) and
    int4 geometry moves land."""
    eng = _engine(tiny_config, params, kv_pages=8, kv_page_size=32,
                  kv_dtype="int4", paged_attn="fold")
    with pytest.raises(ValueError, match="int4-pool -> int8-pool"):
        eng.reconfigure({"slots": 2, "kv_pages": 8, "kv_page_size": 32,
                         "kv_dtype": "int8", "paged_attn": "fold"})
    with pytest.raises(ValueError, match="int4-pool -> float-pool"):
        eng.reconfigure({"slots": 2, "kv_pages": 8, "kv_page_size": 32,
                         "paged_attn": "fold"})
    assert eng.reconfigure({"slots": 4, "kv_pages": 12,
                            "kv_page_size": 32, "kv_dtype": "int4",
                            "paged_attn": "fold"}) is True
    eng2 = _engine(tiny_config, params, kv_pages=8, kv_page_size=32,
                   kv_dtype="int8", paged_attn="fold")
    assert eng2.reconfigure({"slots": 2, "kv_pages": 8,
                             "kv_page_size": 32, "kv_dtype": "int4",
                             "paged_attn": "fold"}) is True
    assert eng2.cache.k.q.dtype == jnp.uint8     # really the packed pool


def test_switch_keeps_matching_host_tier_victim_entries(tiny_config,
                                                        params):
    """The PR 9 gap, closed: victim entries are raw per-page pool
    slices, valid in ANY rebuilt pool with the same page geometry +
    storage dtype — a matching switch must KEEP them (parked and
    preempted streams resume from their pages instead of re-prefilling)
    while prefix entries still die with the registry; a switch that
    changes the storage dtype clears the tier (old-pool bytes would
    scatter stale into the new pool)."""
    from cake_tpu.kv.host_tier import HostTier, SpilledPages

    eng = _engine(tiny_config, params, kv_pages=8, kv_page_size=PAGE,
                  kv_dtype="int8", kv_host_pages=8, paged_attn="fold")
    arrays = HostTier.fetch_pages(eng.cache, [0, 1])
    assert eng._host_tier.put(("victim", 7),
                              SpilledPages(2, arrays, "victim"))
    assert eng._host_tier.put(("prefix", 3),
                              SpilledPages(2, arrays, "prefix"))
    # same geometry + storage dtype, page COUNT and slots move: the
    # victim entry survives, the prefix entry dies with the registry
    assert eng.reconfigure({"slots": 4, "kv_pages": 12,
                            "kv_page_size": PAGE, "kv_dtype": "int8",
                            "paged_attn": "fold"}) is True
    assert eng._host_tier.peek(("victim", 7)) is not None
    assert eng._host_tier.peek(("prefix", 3)) is None
    # storage narrows int8 -> int4: every entry is old-pool bytes now
    assert eng.reconfigure({"slots": 4, "kv_pages": 12,
                            "kv_page_size": PAGE, "kv_dtype": "int4",
                            "paged_attn": "fold"}) is True
    assert eng._host_tier.used_pages == 0


def test_switch_refused_when_a_stream_cannot_fit(tiny_config, params):
    with _engine(tiny_config, params, kv_pages=16, kv_page_size=PAGE,
                 paged_attn="fold") as eng:
        h = eng.submit(PROMPT, max_new_tokens=30)   # needs 3 pages
        _wait_tokens(h, 2)
        # a 2-page pool cannot hold this stream's prompt + budget:
        # refused LOUDLY, and the stream keeps decoding untouched
        with pytest.raises(ValueError,
                           match="no stream may be dropped"):
            eng.reconfigure({"slots": 2, "kv_pages": 2,
                             "kv_page_size": PAGE,
                             "paged_attn": "fold"})
        assert eng.cache.n_pages == 16      # nothing moved
        assert eng.config_epoch == 0
        assert h.wait(120)
        assert h._req.error is None


def test_unsupported_flavor_and_noop_switch(tiny_config, params):
    eng = _engine(tiny_config, params)
    # no-op: the same config (spelled with auto knobs) switches nothing
    assert eng.reconfigure(eng.current_config()) is False
    assert eng.config_epoch == 0
    # unknown knob is a loud client error
    with pytest.raises(ValueError, match="unknown engine config"):
        eng.reconfigure({"slotz": 4})


def test_switch_in_flight_is_exclusive(tiny_config, params):
    with _engine(tiny_config, params) as eng:
        h = eng.submit(PROMPT, max_new_tokens=8)
        eng._switch_inflight = True
        try:
            with pytest.raises(SwitchInFlightError):
                eng.reconfigure({"slots": 4})
        finally:
            eng._switch_inflight = False
        assert h.wait(120)


def test_failed_rebuild_restores_previous_config(tiny_config, params,
                                                 monkeypatch):
    """If the NEW config's pool build fails (e.g. OOM after the old
    pool was freed), the switch rolls back to the previous geometry
    and every folded stream still completes — the engine must never
    be left cacheless."""
    import cake_tpu.models.llama.paged as paged_mod

    with _engine(tiny_config, params) as eng:    # dense, 2 slots
        h = eng.submit(PROMPT, max_new_tokens=20)
        _wait_tokens(h, 4)

        def boom(*_a, **_k):
            raise RuntimeError("synthetic pool OOM")

        monkeypatch.setattr(paged_mod.PagedKVCache, "create", boom)
        with pytest.raises(ValueError, match="previous config"):
            eng.reconfigure({"slots": 4, "kv_pages": 16,
                             "kv_page_size": PAGE,
                             "paged_attn": "fold"})
        # old geometry restored, no epoch bump, stream carried
        assert eng.paged is False and eng.max_slots == 2
        assert eng.cache is not None
        assert eng.config_epoch == 0
        assert eng.stats.config_switches == 0
        assert h.wait(120)
        assert h._req.error is None
        assert len(h._req.out_tokens) == 20


def test_fifo_switch_carries_a_full_queue_plus_active_slots(
        tiny_config, params):
    """FIFO reconfigure rebuilds the scheduler — its capacity must
    cover QUEUED + formerly-ACTIVE requests (active slots never
    counted against the old queue cap), or the overflow would be
    dropped in violation of the zero-dropped-streams contract."""
    with _engine(tiny_config, params, max_queue=2) as eng:
        hs = [eng.submit([5 + i] * 6, max_new_tokens=10)
              for i in range(2)]
        _wait_tokens(hs[0], 2)       # both decoding: slots full
        _wait_tokens(hs[1], 1)
        hs += [eng.submit([9 + i] * 6, max_new_tokens=10)
               for i in range(2)]    # 2 active + 2 queued = cap + 2
        assert eng.reconfigure({"slots": 3}) is True
        assert all(h.wait(120) for h in hs)
        assert [h._req.error for h in hs] == [None] * 4


def test_manual_switch_syncs_the_auto_controller(tiny_config, params):
    """An operator's POST switch on an --autotune auto engine must
    update the controller's notion of "current", or it would keep
    proposing moves relative to the superseded config forever."""
    from cake_tpu.autotune import config_key

    policy = {"version": 1, "regimes": [
        {"max_offered_rps": None,
         "config": {"slots": 2, "kv_pages": 16, "kv_page_size": PAGE,
                    "paged_attn": "fold"}}]}
    eng = _engine(tiny_config, params, kv_pages=16, kv_page_size=PAGE,
                  paged_attn="fold", autotune="auto",
                  autotune_policy=policy)
    assert eng.reconfigure({"slots": 4, "kv_pages": 16,
                            "kv_page_size": PAGE,
                            "paged_attn": "fold"},
                           reason="manual") is True
    assert (config_key(eng._autotuner._current)
            == config_key(eng.current_config()))
    # and the manual reason armed no rollback guard
    assert eng._autotuner._guard is None


CONFIGS = [
    {"slots": 2, "kv_pages": 16, "kv_page_size": PAGE,
     "paged_attn": "fold"},
    {"slots": 3, "kv_pages": 24, "kv_page_size": PAGE,
     "paged_attn": "fold"},
]


@pytest.mark.slow  # 300 random ops with live switches -> slow lane
def test_property_random_submit_cancel_switch(tiny_config, params):
    """300 random submit/cancel/switch steps against a paged engine
    alternating between two pool geometries: after a full drain, every
    stream either completed cleanly or was cancelled by the test (no
    engine-originated errors), and the page pool is exactly conserved
    (free == total; the allocator's own invariants raise on any
    double-free/foreign-page along the way)."""
    rng = random.Random(11)
    kw = {("max_slots" if k == "slots" else k): v
          for k, v in CONFIGS[0].items()}
    eng = _engine(tiny_config, params, **kw)
    live, done, cancelled = [], [], 0
    with eng:
        for step in range(300):
            op = rng.random()
            if op < 0.55:
                h = eng.submit([rng.randrange(3, 60)
                                for _ in range(rng.randrange(3, 12))],
                               max_new_tokens=rng.randrange(2, 8))
                live.append(h)
            elif op < 0.75 and live:
                h = live.pop(rng.randrange(len(live)))
                eng.cancel(h)
                cancelled += 1
            elif op < 0.82:
                target = CONFIGS[(eng.cache.n_pages == 16) * 1]
                eng.reconfigure(target)
            live = [h for h in live if not (h._req.done.is_set()
                                            and done.append(h))]
            if len(live) > 12:
                time.sleep(0.01)
        assert all(h.wait(180) for h in live)
        done.extend(live)
        # engine must not have failed anyone: every non-cancelled
        # stream completed with tokens and no error
        failed = [h for h in done if h._req.error is not None]
        assert failed == []
        # page-refcount conservation after the drain
        deadline = time.perf_counter() + 30
        while (eng._pager.free_pages != eng.cache.n_pages
               and time.perf_counter() < deadline):
            time.sleep(0.01)
        assert eng._pager.free_pages == eng.cache.n_pages
        assert eng.stats.config_switches > 0


def test_api_autotune_contract(tiny_config, params):
    """POST/GET /api/v1/autotune + health config reporting, at the
    ApiServer layer (no HTTP socket: the handler's routing is one
    dispatch away and the 409 mapping is pinned via the typed error)."""
    from cake_tpu.api.server import ApiServer

    class _M:  # master stand-in: ApiServer only reads .args
        args = None

    with _engine(tiny_config, params, autotune="manual") as eng:
        api = ApiServer(_M(), engine=eng)
        h = api.health()
        assert h["engine_config"]["slots"] == 2
        assert h["config_epoch"] == 0
        assert h["autotune"] == "manual"
        state = api.autotune()
        assert state["mode"] == "manual"
        assert state["switches"] == 0
        out = api.autotune_switch({"config": {"slots": 4}})
        assert out["switched"] is True and out["epoch"] == 1
        assert api.health()["engine_config"]["slots"] == 4
        assert api.autotune()["switch_log"][-1]["reason"] == "manual"
        with pytest.raises(ValueError, match="config"):
            api.autotune_switch({})

    with _engine(tiny_config, params) as eng:  # autotune off
        api = ApiServer(_M(), engine=eng)
        assert api.health()["autotune"] == "off"
        with pytest.raises(ValueError, match="autotune is off"):
            api.autotune_switch({"config": {"slots": 4}})


def test_api_autotune_refuses_the_removed_mixed_batch_knob(tiny_config,
                                                           params):
    """`mixed_batch` was a switchable knob until PR 28. A paged engine
    has one prefill path now: a POST body that still carries the key is
    refused by name and nothing switches, and the reported config has
    no such key."""
    from cake_tpu.api.server import ApiServer

    class _M:
        args = None

    with _engine(tiny_config, params, autotune="manual", kv_pages=24,
                 kv_page_size=16) as eng:
        api = ApiServer(_M(), engine=eng)
        assert "mixed_batch" not in api.health()["engine_config"]
        with pytest.raises(ValueError,
                           match="unknown engine config keys .'mixed_batch'"):
            api.autotune_switch({"config": {"slots": 4,
                                            "mixed_batch": "off"}})
        assert api.health()["config_epoch"] == 0
        assert api.health()["engine_config"]["slots"] == 2
