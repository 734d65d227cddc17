"""Serving plane (ISSUE 15): admission-policy goldens, prefill/decode
parity against the training-path logits, continuous-vs-static batching
occupancy, mid-batch retire/admit independence, hot-swap bit-parity vs
cold load, overload shed, autoscale decisions, and THE train→serve
handoff drill (train N steps → commit → the service picks up the new
step → greedy decode matches a fresh single-process load)."""

import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import (Autoscaler, CheckpointWatcher,
                                 DecodeEngine, Request, ServingServer,
                                 desired_np, drive, load_params,
                                 synthetic_workload)
from horovod_tpu.serving import policy as P
from horovod_tpu.serving.submit import generate
from horovod_tpu.runner.rendezvous import _signature

CFG = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=64, n_layers=2,
    seq_len=64, dtype=jnp.float32, remat=False)
PAGE = 8


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(0), CFG,
                           tfm.ParallelConfig())


def _engine(params, slots=4, **kw):
    kw.setdefault("page_tokens", PAGE)
    kw.setdefault("max_len", CFG.seq_len)
    return DecodeEngine(CFG, params, slots=slots, **kw)


def _greedy(engine, prompt, n):
    """Run one request to completion on an otherwise idle engine."""
    evs = engine.admit(Request(id="g", prompt=list(prompt),
                               max_new_tokens=n))
    toks = [e.token for e in evs if e.kind == "token"]
    while not any(e.kind == "finish" for e in evs):
        evs = engine.step()
        toks += [e.token for e in evs if e.kind == "token"]
    return toks


# ---------------------------------------------------------------------------
# Policy goldens (pure plan)
# ---------------------------------------------------------------------------

def _rv(i, **kw):
    kw.setdefault("tenant", "default")
    kw.setdefault("pages_needed", 1)
    return P.RequestView(id=f"r{i}", submit_seq=i, **kw)


def test_policy_priority_then_fifo():
    out = P.plan([_rv(0), _rv(1, priority=5), _rv(2)],
                 free_slots=2, free_pages=10, now_s=0.0)
    assert out == [("admit", "r1"), ("admit", "r0"), ("wait", "r2",
                                                      "slots")]


def test_policy_fair_share_and_deadline():
    # Tenant b already holds 2 slots → tenant a goes first at equal
    # priority; among a's requests the tighter deadline wins over FIFO.
    views = [_rv(0, tenant="b"),
             _rv(1, tenant="a", deadline_s=5.0),
             _rv(2, tenant="a", deadline_s=1.0)]
    out = P.plan(views, free_slots=2, free_pages=10, now_s=0.0,
                 running={"b": 2})
    assert out == [("admit", "r2"), ("admit", "r1"),
                   ("wait", "r0", "slots")]


def test_policy_shed_deadline_and_overload():
    views = [_rv(0, deadline_s=1.0, arrival_s=0.0),        # blown
             _rv(1), _rv(2), _rv(3, priority=9)]
    out = P.plan(views, free_slots=0, free_pages=10, now_s=5.0,
                 queue_cap=2)
    sheds = {d[1]: d[2] for d in out if d[0] == "shed"}
    # r0 shed on deadline; over the cap of 2, the lowest-priority
    # newest (r2) sheds; r3's priority protects it.
    assert sheds == {"r0": "deadline", "r2": "overload"}
    waits = [d[1] for d in out if d[0] == "wait"]
    assert waits == ["r3", "r1"]


def test_policy_fair_share_within_one_plan():
    # Each admit updates the fair-share key: a burst tenant must NOT
    # take every free slot in a single planning pass.
    views = [_rv(0, tenant="a"), _rv(1, tenant="a"), _rv(2, tenant="b")]
    out = P.plan(views, free_slots=2, free_pages=10, now_s=0.0)
    assert out == [("admit", "r0"), ("admit", "r2"),
                   ("wait", "r1", "slots")]


def test_policy_sheds_request_larger_than_any_slot():
    views = [_rv(0, pages_needed=9), _rv(1, pages_needed=2)]
    out = P.plan(views, free_slots=2, free_pages=16, now_s=0.0,
                 slot_pages=8)
    assert ("shed", "r0", "too_large") in out
    assert ("admit", "r1") in out


def test_policy_no_head_of_line_blocking():
    views = [_rv(0, pages_needed=8), _rv(1, pages_needed=2)]
    out = P.plan(views, free_slots=2, free_pages=4, now_s=0.0)
    assert ("wait", "r0", "pages") in out
    assert ("admit", "r1") in out


def test_policy_deterministic():
    views = [_rv(i, priority=i % 3, tenant=f"t{i % 2}")
             for i in range(6)]
    a = P.plan(list(views), 2, 10, now_s=0.0)
    b = P.plan(list(reversed(views)), 2, 10, now_s=0.0)
    assert a == b


# ---------------------------------------------------------------------------
# Prefill / decode parity vs the training path
# ---------------------------------------------------------------------------

def test_prefill_matches_training_logits(params):
    prompt = np.array([3, 9, 1, 17, 30, 2, 5, 11], np.int32)  # == 1 page
    kv = tfm.init_kv_pages(CFG, n_pages=3, page_size=PAGE)
    logits, kv = tfm.chunk_forward(CFG, params, jnp.asarray(prompt)[None],
                                   jnp.zeros((1,), jnp.int32), kv,
                                   jnp.asarray([[1]], jnp.int32))
    logits = logits[0, len(prompt) - 1]
    oracle = tfm.serial_forward_logits(CFG, params,
                                       jnp.asarray(prompt)[None])
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(oracle[0, -1]),
                               rtol=1e-4, atol=1e-5)
    assert int(np.argmax(logits)) == int(np.argmax(oracle[0, -1]))


def test_prefill_padded_prompt_matches(params):
    # Prompt NOT a page multiple: padded tail must not leak into the
    # last valid position's logits (causality).
    prompt = np.array([7, 2, 40, 13, 22], np.int32)
    kv = tfm.init_kv_pages(CFG, n_pages=3, page_size=PAGE)
    tokens = np.full((PAGE,), 63, np.int32)
    tokens[:5] = prompt
    logits, _ = tfm.chunk_forward(CFG, params, jnp.asarray(tokens)[None],
                                  jnp.zeros((1,), jnp.int32), kv,
                                  jnp.asarray([[1]], jnp.int32))
    logits = logits[0, 4]
    oracle = tfm.serial_forward_logits(CFG, params,
                                       jnp.asarray(prompt)[None])
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(oracle[0, -1]),
                               rtol=1e-4, atol=1e-5)


def test_decode_tick_matches_training_logits(params):
    # Greedy-generate 6 tokens through the paged decode path; every
    # step's next-token distribution must match the training-path
    # forward over the growing sequence (fp32-accumulation caveats →
    # tight allclose + argmax, not bit equality; see transformer.py).
    prompt = [3, 9, 1, 17, 30, 2, 5, 11]
    eng = _engine(params, slots=2)
    evs = eng.admit(Request(id="a", prompt=prompt, max_new_tokens=7))
    seq = list(prompt) + [evs[0].token]
    oracle = tfm.serial_forward_logits(
        CFG, params, jnp.asarray(np.array(prompt, np.int32))[None])
    assert evs[0].token == int(np.argmax(oracle[0, -1]))
    for _ in range(6):
        evs = eng.step()
        tok = [e for e in evs if e.kind == "token"][0].token
        oracle = tfm.serial_forward_logits(
            CFG, params, jnp.asarray(np.array(seq, np.int32))[None])
        assert tok == int(np.argmax(oracle[0, -1]))
        seq.append(tok)


@pytest.mark.parametrize("kq,length", [(1, 16), (4, 16), (4, 14)],
                         ids=["k1-at-extent", "k4-at-extent",
                              "k4-crossing"])
def test_positions_past_the_table_extent_write_scratch_only(params, kq,
                                                            length):
    # chunk_forward's overflow contract: a position at or past the
    # table's extent (2 pages = 16 positions here) writes scratch page 0
    # and nothing else — not the slot's last page (where the clamped
    # index points), not a neighbour's.
    rng = np.random.default_rng(0)
    kv = tfm.init_kv_pages(CFG, n_pages=5, page_size=PAGE)
    full = rng.integers(0, CFG.vocab_size, (2, 16)).astype(np.int32)
    _, kv = tfm.chunk_forward(
        CFG, params, jnp.asarray(full), jnp.zeros((2,), jnp.int32), kv,
        jnp.asarray([[1, 2], [3, 4]], jnp.int32))
    before = {n: np.asarray(a) for n, a in kv.items()}
    table = jnp.asarray([[1, 2]], jnp.int32)
    chunk = rng.integers(0, CFG.vocab_size, (1, kq)).astype(np.int32)
    n_in = max(0, 16 - length)
    want = dict(before)
    if n_in:
        # What the in-range head of the chunk writes on its own.
        _, head = tfm.chunk_forward(
            CFG, params, jnp.asarray(chunk[:, :n_in]),
            jnp.asarray([length], jnp.int32), dict(kv), table)
        want = {n: np.asarray(a) for n, a in head.items()}
        assert not np.array_equal(want["k"][:, 2], before["k"][:, 2])
    logits, after = tfm.chunk_forward(
        CFG, params, jnp.asarray(chunk), jnp.asarray([length], jnp.int32),
        dict(kv), table)
    assert np.all(np.isfinite(np.asarray(logits)))
    for n in ("k", "v"):
        got = np.asarray(after[n])
        # Pages 1, 3, 4 and every row below ``length``: bit for bit.
        np.testing.assert_array_equal(got[:, [1, 3, 4]],
                                      before[n][:, [1, 3, 4]])
        np.testing.assert_array_equal(got[:, 2, :length - PAGE],
                                      before[n][:, 2, :length - PAGE])
        # Rows from ``length`` up: the in-range head's own K/V, which
        # an overflow write through the clamped index would replace.
        np.testing.assert_allclose(got[:, 2], want[n][:, 2],
                                   rtol=1e-5, atol=1e-6)
        # Scratch: only the clamped offset's row is written.
        assert np.any(got[:, 0, PAGE - 1] != 0.0)
        np.testing.assert_array_equal(got[:, 0, :PAGE - 1], 0.0)


def test_kv_page_geometry():
    kv = tfm.init_kv_pages(CFG, n_pages=5, page_size=4)
    assert kv["k"].shape == (CFG.n_layers, 5, 4, CFG.n_heads,
                             CFG.head_dim)
    assert kv["k"].dtype == CFG.dtype


# ---------------------------------------------------------------------------
# Engine: continuous batching, recompiles, independence, pages
# ---------------------------------------------------------------------------

def test_admission_never_recompiles(params):
    eng = _engine(params, slots=3)
    sched = synthetic_workload(1, 10, rate_rps=0.0, prompt_lens=(3, 20),
                               output_lens=(2, 9), vocab=CFG.vocab_size)
    out = drive(eng, sched, continuous=True)
    assert len([r for r in out["results"].values() if "tokens" in r]) == 10
    # ONE decode compile across every admit/retire recomposition; the
    # prompt mix above spans at most three power-of-two prefill buckets.
    assert eng.decode_traces == 1
    assert eng.prefill_traces <= 3
    # All pages and slots returned.
    assert eng.free_slots() == 3
    assert eng.free_pages() == 3 * eng.pages_per_slot


def test_co_batched_outputs_independent(params):
    # The same request decodes to the SAME tokens alone and co-batched
    # with arbitrary neighbors (batch recomposition cannot change a
    # request's output).
    sched = synthetic_workload(2, 6, rate_rps=0.0, prompt_lens=(4, 12),
                               output_lens=(3, 8), vocab=CFG.vocab_size)
    batched = drive(_engine(params, slots=3), sched, continuous=True)
    for _, req in sched:
        alone = _greedy(_engine(params, slots=3), req.prompt,
                        req.max_new_tokens)
        assert alone == batched["results"][req.id]["tokens"], req.id


def test_continuous_beats_static_occupancy(params):
    def _sched():
        return synthetic_workload(3, 12, rate_rps=0.0,
                                  prompt_lens=(4, 12),
                                  output_lens=(2, 12),
                                  vocab=CFG.vocab_size)
    cont = drive(_engine(params, slots=4), _sched(), continuous=True)
    stat = drive(_engine(params, slots=4), _sched(), continuous=False)
    assert cont["occupancy"] > stat["occupancy"]
    # Same outputs either way — batching policy is a throughput knob,
    # never a correctness one.
    for rid, r in cont["results"].items():
        assert r["tokens"] == stat["results"][rid]["tokens"]


def test_geometry_validation_and_loud_refusals(params):
    # max_len rounds DOWN to a page multiple (a partial tail page would
    # overrun the positional table in a full prompt's padded prefill).
    eng = _engine(params, slots=1, max_len=60)
    assert eng.max_len == 56 and eng.pages_per_slot == 7
    with pytest.raises(ValueError):
        _engine(params, slots=1, max_len=4)
    # Bypassing the policy must fail loudly, never corrupt the pool.
    starved = _engine(params, slots=1, total_pages=1)
    with pytest.raises(RuntimeError):
        starved.admit(Request(id="a", prompt=list(range(20)),
                              max_new_tokens=30))
    assert starved.free_pages() == 1 and starved.free_slots() == 1


def test_page_pool_accounting(params):
    eng = _engine(params, slots=2, total_pages=4)
    evs = eng.admit(Request(id="a", prompt=[1, 2, 3], max_new_tokens=4))
    assert eng.free_pages() == 3     # ceil((3+4)/8) = 1 page reserved
    big = Request(id="b", prompt=list(range(20)), max_new_tokens=30)
    assert big.pages_needed(PAGE) == 7
    # The policy would hold 'b' (pages), so the engine never sees it;
    # finishing 'a' returns its reservation.
    while not any(e.kind == "finish" for e in evs):
        evs = eng.step()
    assert eng.free_pages() == 4 and eng.free_slots() == 2


# ---------------------------------------------------------------------------
# Request plane: HTTP roundtrip, auth, shed, metrics
# ---------------------------------------------------------------------------

def test_http_roundtrip_stream_and_auth(params):
    eng = _engine(params, slots=2)
    srv = ServingServer(eng, port=0, secret="s3cret", queue_cap=8)
    port = srv.serve()
    addr = f"127.0.0.1:{port}"
    try:
        h = json.loads(urllib.request.urlopen(
            f"http://{addr}/serve/healthz", timeout=5).read())
        assert h["service"] == "horovod_tpu_serving"
        body = json.dumps({"tokens": [1, 2, 3]}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"http://{addr}/serve/generate", data=body), timeout=5)
        assert ei.value.code == 403
        out = generate({"tokens": [1, 2, 3, 4], "max_new_tokens": 5},
                       server=addr, secret="s3cret")
        assert len(out["tokens"]) == 5 and out["reason"] == "length"
        assert out["ttft_s"] is not None
        body = json.dumps({"tokens": [5, 6, 7], "max_new_tokens": 4,
                           "stream": True}).encode()
        req = urllib.request.Request(
            f"http://{addr}/serve/generate", data=body)
        req.add_header("X-HVD-Signature",
                       _signature("s3cret", "POST", "serve",
                                  "generate", body))
        lines = [json.loads(l) for l in
                 urllib.request.urlopen(req, timeout=30)]
        toks = [l["token"] for l in lines if "token" in l]
        assert lines[-1]["done"] and lines[-1]["tokens"] == toks
        assert "ttft_s" in lines[0]
        # Matches the engine driven directly (same weights, greedy).
        assert toks == _greedy(_engine(params, slots=2), [5, 6, 7], 4)
    finally:
        srv.close()


def test_overload_shed_is_loud(params):
    from horovod_tpu.metrics.registry import registry
    shed0 = registry().counter("hvd_serving_shed_total",
                               "", reason="overload").value
    eng = _engine(params, slots=1)
    srv = ServingServer(eng, port=0, secret=None, queue_cap=1)
    # Not serve()d: the loop never drains, so the queue stays full —
    # a deterministic overload.
    ok1 = srv.submit(Request(id="q1", prompt=[1], max_new_tokens=2,
                             arrival_mono=time.monotonic()),
                     __import__("queue").Queue())
    ok2 = srv.submit(Request(id="q2", prompt=[1], max_new_tokens=2,
                             arrival_mono=time.monotonic()),
                     __import__("queue").Queue())
    assert ok1 and not ok2
    assert registry().counter("hvd_serving_shed_total", "",
                              reason="overload").value == shed0 + 1
    snap = hvd.debug.flight.snapshot()
    ev = [e for e in snap if e.get("kind") == "serving.shed"]
    assert ev and ev[-1]["name"] == "q2"
    srv.stop()   # only the HTTP socket was bound


def test_duplicate_request_ids_survive(params):
    # A client retry reusing its id must not collide with the
    # in-flight original (it used to kill the serving loop thread).
    eng = _engine(params, slots=2)
    srv = ServingServer(eng, port=0, secret=None, queue_cap=8)
    srv.serve()
    try:
        addr = f"127.0.0.1:{srv.port}"
        import threading
        outs = [None, None]

        def _go(i):
            outs[i] = generate({"id": "dup", "tokens": [1, 2, 3],
                                "max_new_tokens": 4}, server=addr)
        ts = [threading.Thread(target=_go, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert all(o and len(o["tokens"]) == 4 for o in outs), outs
        assert srv._loop_thread.is_alive()
        # Both served (one id uniquified), identical outputs.
        assert outs[0]["tokens"] == outs[1]["tokens"]
    finally:
        srv.close()


def test_oversized_request_sheds_not_livelocks(params):
    # Engine whose pool is smaller than a slot's worth: an impossible
    # request must shed (reason capacity/too_large), not spin drive()
    # forever or crash it.
    eng = _engine(params, slots=2, total_pages=2)
    reqs = [(0.0, Request(id="big", prompt=list(range(10)),
                          max_new_tokens=20, submit_seq=0)),
            (0.0, Request(id="ok", prompt=[1, 2], max_new_tokens=4,
                          submit_seq=1))]
    out = drive(eng, reqs, continuous=True)
    assert out["results"]["big"]["shed"] in ("too_large", "capacity")
    assert out["results"]["ok"]["tokens"]


def test_shed_vocabulary_classified():
    from horovod_tpu.debug.regression import _classify
    assert _classify("serving.swap") == "serving"
    assert _classify("serving.admit") == "serving"
    assert _classify("serving.shed") == "serving"
    assert _classify("serving.autoscale") == "serving"
    assert _classify("serving.retire") == "serving"   # prefix family


# ---------------------------------------------------------------------------
# Hot swap + THE train→serve handoff drill
# ---------------------------------------------------------------------------

def _train_commit(ckpt_dir, steps, start_step, params, opt_state,
                  train_step, tokens, labels):
    for _ in range(steps):
        params, opt_state, _ = train_step(params, opt_state, tokens,
                                          labels)
    from horovod_tpu.checkpoint import save_zero_state
    save_zero_state(ckpt_dir, params, step=start_step + steps)
    return params, opt_state, start_step + steps


def test_handoff_drill_and_hot_swap_bit_parity(tmp_path):
    """Train → commit → serve → train more → commit → hot-swap between
    decode iterations → greedy decode bit-identical (float ==) to a
    fresh single-process load of the new step."""
    import optax
    from horovod_tpu.parallel.mesh import create_mesh
    hvd.init()
    mesh = create_mesh({"dp": 1, "pp": 1, "mp": 1})
    par = tfm.ParallelConfig()
    params = tfm.init_params(jax.random.PRNGKey(0), CFG, par)
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    train_step, shard = tfm.make_train_step(CFG, par, mesh, tx)
    tokens, labels = tfm.synthetic_batch(jax.random.PRNGKey(1), CFG, 2)
    ckpt = str(tmp_path / "ckpt")
    params, opt_state, step = _train_commit(
        ckpt, 2, 0, params, opt_state, train_step, tokens, labels)

    like = tfm.init_params(jax.random.PRNGKey(9), CFG, par)
    p0, s0 = load_params(ckpt, like)
    assert s0 == step
    eng = _engine(p0, slots=2, params_tag=s0)
    watcher = CheckpointWatcher(eng, ckpt, like, poll_s=0.05)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    before = _greedy(eng, prompt, 6)

    # The training job commits a newer step; the service picks it up.
    params, opt_state, step = _train_commit(
        ckpt, 2, step, params, opt_state, train_step, tokens, labels)
    assert watcher.check_once() == step
    hot = _greedy(eng, prompt, 6)          # swap applies at admit/step
    assert eng.params_tag == step

    # Fresh single-process cold load of the same step.
    p2, s2 = load_params(ckpt, like)
    assert s2 == step
    cold_eng = _engine(p2, slots=2, params_tag=s2)
    cold = _greedy(cold_eng, prompt, 6)
    assert hot == cold
    # Bit-identical weights (float ==), not just greedy agreement —
    # the engine passes the swapped tree through untransformed.
    for a, b in zip(jax.tree_util.tree_leaves(eng._params),
                    jax.tree_util.tree_leaves(p2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # Training really moved the weights (the swap was observable).
    assert hot != before
    from horovod_tpu.metrics.registry import registry
    assert registry().counter("hvd_serving_swaps_total", "").value >= 1


def test_watcher_thread_picks_up_commit(tmp_path):
    hvd.init()
    par = tfm.ParallelConfig()
    params = tfm.init_params(jax.random.PRNGKey(0), CFG, par)
    ckpt = str(tmp_path / "ckpt")
    from horovod_tpu.checkpoint import save_zero_state
    save_zero_state(ckpt, params, step=1)
    like = tfm.init_params(jax.random.PRNGKey(9), CFG, par)
    p, s = load_params(ckpt, like)
    eng = _engine(p, slots=1, params_tag=s)
    w = CheckpointWatcher(eng, ckpt, like, poll_s=0.05)
    w.start()
    try:
        save_zero_state(
            ckpt, jax.tree_util.tree_map(lambda a: a * 1.5, params),
            step=2)
        deadline = time.monotonic() + 5
        while w.current_step != 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert w.current_step == 2
        eng.step()   # applies the parked swap
        assert eng.params_tag == 2
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# Autoscale + fleet integration
# ---------------------------------------------------------------------------

def test_desired_np_goldens():
    # Queue pressure scales up one step.
    assert desired_np(2, 1, 8, queue_depth=9, target_queue=4.0) == 3
    # At target: hold.
    assert desired_np(2, 1, 8, queue_depth=8, target_queue=4.0) == 2
    # Empty queue + idle slots scales down.
    assert desired_np(2, 1, 8, queue_depth=0, target_queue=4.0) == 1
    # A saturated replica whose queue merely drained between ticks is
    # NOT idle: busy slots hold the width.
    assert desired_np(2, 1, 8, queue_depth=0, target_queue=4.0,
                      occupancy=1.0) == 2
    # SLO pressure scales up even with a short queue.
    assert desired_np(2, 1, 8, queue_depth=1, target_queue=4.0,
                      ttft_p95=2.0, slo_ttft_s=1.0) == 3
    # SLO headroom required before scale-down.
    assert desired_np(2, 1, 8, queue_depth=0, target_queue=4.0,
                      ttft_p95=0.9, slo_ttft_s=1.0) == 2
    # Clamped to [min, max].
    assert desired_np(1, 1, 8, queue_depth=0, target_queue=4.0) == 1
    assert desired_np(8, 1, 8, queue_depth=99, target_queue=4.0) == 8


class _FakeDriver:
    def __init__(self):
        self.calls = []

    def request_resize(self, np_, reason):
        self.calls.append((np_, reason))
        return True


def test_autoscaler_drives_request_resize():
    drv = _FakeDriver()
    status = {"np": 1, "queue_depth": 10, "ttft_p95": 0.0}
    a = Autoscaler(drv, lambda: status, min_np=1, max_np=4,
                   target_queue=4.0, slo_ttft_s=0.0, cooldown_s=100.0)
    assert a.maybe_resize(now=1000.0) == 2
    assert drv.calls[-1][0] == 2
    # Cooldown hysteresis: pressure still high, but no flapping.
    assert a.maybe_resize(now=1001.0) is None
    # After the cooldown, idle queue scales back down.
    status.update(np=2, queue_depth=0)
    assert a.maybe_resize(now=2000.0) == 1
    assert [c[0] for c in drv.calls] == [2, 1]


def test_jobspec_kind_service_roundtrip():
    from horovod_tpu.fleet.job import JobSpec
    spec = JobSpec(command=["python", "-m", "serve"], kind="service",
                   min_np=1, max_np=4)
    assert spec.validate() is None
    again = JobSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert again.kind == "service"
    # Old records without the field stay batch jobs.
    d = spec.to_dict()
    d.pop("kind")
    assert JobSpec.from_dict(d).kind == "batch"
    assert "kind" in JobSpec(command=["x"], kind="cron").validate()


def test_fleet_submit_cli_builds_service_spec():
    from horovod_tpu.fleet.submit import build_spec, parse_args
    args = parse_args(["--kind", "service", "-np", "2", "--",
                       "python", "-m", "serve"])
    spec = build_spec(args)
    assert spec.kind == "service" and spec.min_np == 2
    assert spec.validate() is None


def test_fleet_runner_exports_job_kind():
    from horovod_tpu.fleet.job import JobRecord, JobSpec
    from horovod_tpu.fleet.scheduler import ElasticJobRunner
    rec = JobRecord(id="svc1", spec=JobSpec(
        command=["python", "-c", "pass"], kind="service"))
    runner = ElasticJobRunner(rec, {})
    env = runner._driver._extra_env
    assert env["HVD_TPU_FLEET_JOB_KIND"] == "service"
    assert env["HVD_TPU_FLEET_JOB_ID"] == "svc1"


def test_serving_config_knobs(monkeypatch):
    from horovod_tpu.core.config import Config
    monkeypatch.setenv("HVD_TPU_SERVING_SLOTS", "0")       # clamped
    monkeypatch.setenv("HVD_TPU_SERVING_PAGE_TOKENS", "32")
    monkeypatch.setenv("HVD_TPU_SERVING_QUEUE_CAP", "7")
    monkeypatch.setenv("HVD_TPU_SERVING_SWAP_POLL_S", "0.0")  # clamped
    monkeypatch.setenv("HVD_TPU_SERVING_AUTOSCALE", "1")
    cfg = Config.from_env()
    assert cfg.serving_slots == 1
    assert cfg.serving_page_tokens == 32
    assert cfg.serving_queue_cap == 7
    assert cfg.serving_swap_poll_s == 0.05
    assert cfg.serving_autoscale is True
    assert cfg.serving_port == 28643
