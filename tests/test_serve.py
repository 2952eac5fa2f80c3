"""Serving engine: continuous batching correctness + slot lifecycle."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import api, get_config
from repro.serve import Engine, Request

RNG = jax.random.PRNGKey(0)


def _greedy_reference(cfg, params, prompt, n_new, max_seq=48):
    cache = api.init_cache(cfg, 1, max_seq)
    lg, cache = api.prefill(params, cfg, cache,
                            {"tokens": jnp.asarray(prompt)[None]})
    out = [int(jnp.argmax(lg[0]))]
    for _ in range(n_new - 1):
        lg, cache = api.decode_step(params, cfg, cache,
                                    jnp.asarray([out[-1]], jnp.int32))
        out.append(int(jnp.argmax(lg[0])))
    return out


@pytest.mark.parametrize("arch", ["qwen2-1.5b-smoke", "mamba2-1.3b-smoke"])
def test_continuous_batching_exact(arch):
    cfg = get_config(arch)
    params = api.init(RNG, cfg)
    prompt = np.array([5, 6, 7, 8], np.int32)
    ref = _greedy_reference(cfg, params, prompt, 6)
    eng = Engine(cfg, params, slots=3, max_seq=48)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=6))
    eng.submit(Request(uid=1, prompt=np.array([1, 2], np.int32),
                       max_new_tokens=3))
    eng.submit(Request(uid=2, prompt=np.array([9, 9, 9], np.int32),
                       max_new_tokens=8))
    done = eng.run_until_drained()
    got = [r for r in done if r.uid == 0][0].output
    assert got == ref


def test_slot_reuse_and_drain():
    cfg = get_config("qwen2-1.5b-smoke")
    params = api.init(RNG, cfg)
    eng = Engine(cfg, params, slots=2, max_seq=48)
    rng = np.random.default_rng(0)
    for i in range(7):                      # more requests than slots
        eng.submit(Request(uid=i,
                           prompt=rng.integers(0, cfg.vocab_size, 4),
                           max_new_tokens=4))
    done = eng.run_until_drained()
    assert len(done) == 7
    assert all(len(r.output) == 4 for r in done)
    assert eng.stats()["active"] == 0 and eng.stats()["queued"] == 0


def test_requests_respect_max_seq_cap():
    cfg = get_config("qwen2-1.5b-smoke")
    params = api.init(RNG, cfg)
    eng = Engine(cfg, params, slots=1, max_seq=12)
    eng.submit(Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                       max_new_tokens=100))
    done = eng.run_until_drained()
    assert done[0].done
    assert len(done[0].output) <= 12 - 8 + 1


# -- edge cases the seed suite missed ----------------------------------------

def test_oversized_prompt_rejected_not_spliced():
    """A prompt of length >= max_seq must be rejected at submit: splicing
    it would clamp writes into the last cache row (jax .at[].set is
    silent on out-of-bounds) and corrupt whoever shares the pool."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = api.init(RNG, cfg)
    eng = Engine(cfg, params, slots=2, max_seq=8)
    too_long = Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                       max_new_tokens=4)
    assert eng.submit(too_long) is False
    assert too_long.rejected and too_long.done and too_long.output == []
    assert eng.stats()["queued"] == 0          # never entered the queue

    # and the rejection must not perturb a co-resident request:
    prompt = np.array([3, 1, 4], np.int32)
    ref = _greedy_reference(cfg, params, prompt, 4, max_seq=8)
    ok = Request(uid=1, prompt=prompt, max_new_tokens=4)
    assert eng.submit(ok) is True
    done = eng.run_until_drained()
    assert [r.uid for r in done] == [1]
    assert done[0].output == ref and not done[0].rejected


def test_zero_max_new_tokens_completes_immediately():
    """max_new_tokens=0 has nothing to generate: it must complete on the
    admission pass with an empty output instead of occupying a slot
    through a decode step (the seed engine emitted 2 tokens for it)."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = api.init(RNG, cfg)
    eng = Engine(cfg, params, slots=1, max_seq=48)
    eng.submit(Request(uid=0, prompt=np.array([1, 2], np.int32),
                       max_new_tokens=0))
    done = eng.step()
    assert [r.uid for r in done] == [0]
    assert done[0].done and done[0].output == []
    assert eng.stats()["active"] == 0 and eng.stats()["prefills"] == 0
    assert eng.stats()["decode_steps"] == 0    # no decode was spent on it


def test_zero_max_new_does_not_starve_the_slot():
    """With one slot, a zero-token request ahead of a real one must not
    block it (the seed engine pinned the slot for an iteration)."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = api.init(RNG, cfg)
    eng = Engine(cfg, params, slots=1, max_seq=48)
    eng.submit(Request(uid=0, prompt=np.array([1], np.int32),
                       max_new_tokens=0))
    eng.submit(Request(uid=1, prompt=np.array([2, 3], np.int32),
                       max_new_tokens=3))
    done = eng.step()                          # one iteration admits both
    assert 0 in {r.uid for r in done}
    done += eng.run_until_drained()
    by_uid = {r.uid: r for r in done}
    assert len(by_uid[1].output) == 3 and by_uid[1].done


def test_single_token_request_stops_at_prefill():
    """max_new_tokens=1 is satisfied by the prefill argmax alone; the
    seed engine over-generated a second token and burned a decode."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = api.init(RNG, cfg)
    prompt = np.array([5, 6, 7], np.int32)
    ref = _greedy_reference(cfg, params, prompt, 1)
    eng = Engine(cfg, params, slots=2, max_seq=48)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=1))
    done = eng.run_until_drained()
    assert done[0].output == ref and len(done[0].output) == 1
    assert eng.stats()["decode_steps"] == 0


# -- instrumentation: spans, counters, stamps, named programs and scopes ------

def _host_events(out_dir):
    """(name, start_ns, end_ns, stats) of every host event the profiler
    wrote under ``out_dir``."""
    import pathlib
    from jax.profiler import ProfileData
    evs = []
    for path in pathlib.Path(out_dir).rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                evs.extend((e.name, e.start_ns, e.end_ns,
                            dict(e.stats) if e.name.startswith("serve.")
                            else {}) for e in line.events)
    return evs


def test_engine_spans_nest_under_profiler(tmp_path):
    """Each phase of a step writes its span, per phase and never per slot:
    admit holds one prefill and one splice per admitted request (the
    prefill carrying its uid and prompt length), and decode and retire
    follow, all inside the caller's span."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = api.init(RNG, cfg)
    eng = Engine(cfg, params, slots=2, max_seq=48)
    eng.submit(Request(uid=0, prompt=np.array([1, 2], np.int32),
                       max_new_tokens=2))
    eng.run_until_drained()                    # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("caller"):
        eng.submit(Request(uid=11, prompt=np.array([4, 5, 6], np.int32),
                           max_new_tokens=3))
        eng.submit(Request(uid=12, prompt=np.array([7, 8], np.int32),
                           max_new_tokens=2))
        eng.run_until_drained()
    jax.profiler.stop_trace()
    evs = _host_events(tmp_path)
    (caller,) = [e for e in evs if e[0] == "caller"]
    serve = sorted((e for e in evs if e[0].startswith("serve.")),
                   key=lambda e: e[1])
    assert all(caller[1] <= e[1] and e[2] <= caller[2] for e in serve)

    def named(n):
        return [e for e in serve if e[0] == n]
    (admit,) = named("serve.admit")
    inside = [e for e in serve if admit[1] <= e[1] and e[2] <= admit[2]
              and e is not admit]
    assert [e[0] for e in inside] == ["serve.prefill", "serve.splice"] * 2
    assert [(e[3]["uid"], e[3]["prompt_len"])
            for e in named("serve.prefill")] == [(11, 3), (12, 2)]
    assert [e[3]["uid"] for e in named("serve.splice")] == [11, 12]
    # 2 decode steps: both slots, then the longer request alone
    assert [e[3]["batch"] for e in named("serve.decode")] == [2, 1]
    phases = [e[0] for e in serve if e[0] in
              ("serve.admit", "serve.decode", "serve.retire")]
    assert phases == ["serve.admit", "serve.decode", "serve.retire",
                      "serve.decode", "serve.retire"]
    top = [e for e in serve if e[0] != "serve.prefill"
           and e[0] != "serve.splice"]
    assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))


def test_engine_counters_and_stamps_exact():
    """Two requests of known lengths on two slots: every host sync, decode
    step, active slot and prompt token is counted, and the stamps order
    the second admission behind the first one's prefill."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = api.init(RNG, cfg)
    eng = Engine(cfg, params, slots=2, max_seq=48)
    a = Request(uid=0, prompt=np.arange(5, dtype=np.int32), max_new_tokens=4)
    b = Request(uid=1, prompt=np.arange(3, dtype=np.int32), max_new_tokens=2)
    big = Request(uid=2, prompt=np.arange(48, dtype=np.int32))
    empty = Request(uid=3, prompt=np.array([1], np.int32), max_new_tokens=0)
    for r in (a, b, big, empty):
        eng.submit(r)
    eng.run_until_drained()
    # step 1: two prefills (1 read each), decode of 2 slots (1 read for
    # the batch), b done; steps 2, 3: a alone (1 read each)
    assert eng.stats() == {"decode_steps": 3, "prefills": 2,
                           "prefill_tokens": 8, "slot_steps": 4,
                           "host_syncs": 2 + 3, "active": 0, "queued": 0}
    assert len(a.output) == 4 and len(b.output) == 2
    assert a.t_submit <= b.t_submit <= a.t_admit < b.t_admit
    assert big.rejected and big.t_submit is not None and big.t_admit is None
    assert empty.done and empty.t_submit <= a.t_admit and \
        empty.t_admit is None


class _ReadSpy:
    """Counts the host's reads of device arrays: ``jax.device_get`` calls,
    and scalar conversions of a ``jax.Array`` (``int(x[slot])``, say)."""

    SCALAR = ("__int__", "__index__", "__float__", "__bool__", "item")

    def __init__(self, monkeypatch):
        array_type = type(jnp.zeros(()))
        self.device_gets = self.scalars = 0
        get = jax.device_get

        def device_get(x):
            self.device_gets += 1
            return get(x)
        monkeypatch.setattr(jax, "device_get", device_get)
        for name in self.SCALAR:
            monkeypatch.setattr(array_type, name,
                                self._counted(getattr(array_type, name)))

    def _counted(self, method):
        def wrapper(arr, *a, **k):
            self.scalars += 1
            return method(arr, *a, **k)
        return wrapper


@pytest.mark.parametrize("active", [1, 3])
def test_host_reads_per_step_do_not_grow_with_batch(active, monkeypatch):
    """Retiring reads every slot's token and position in one transfer: a
    decode step costs one host read at 1 active slot and at 3, and no
    read is made per slot."""
    cfg = get_config("qwen2-1.5b-smoke")
    params = api.init(RNG, cfg)
    eng = Engine(cfg, params, slots=3, max_seq=48)
    eng.submit(Request(uid=-1, prompt=np.array([1, 2], np.int32),
                       max_new_tokens=2))
    eng.run_until_drained()                  # compile outside the count
    before = eng.stats()
    spy = _ReadSpy(monkeypatch)
    for i in range(active):
        eng.submit(Request(uid=i, prompt=np.array([3, 4 + i], np.int32),
                           max_new_tokens=5))
    done = eng.run_until_drained()
    after = eng.stats()
    assert sorted(r.uid for r in done) == list(range(active))
    assert all(len(r.output) == 5 for r in done)
    steps = after["decode_steps"] - before["decode_steps"]
    prefills = after["prefills"] - before["prefills"]
    assert (steps, prefills) == (4, active)
    assert after["slot_steps"] - before["slot_steps"] == 4 * active
    assert after["host_syncs"] - before["host_syncs"] - prefills == steps
    # one device_get a step; the only scalar reads are the prefill tokens
    assert spy.device_gets == steps
    assert spy.scalars == prefills


def _engine_cut(ref, eos, max_new, prompt_len, max_seq):
    """Greedy tokens ``ref`` cut where the engine stops a request: at its
    first EOS, at ``max_new`` tokens, or at the token decoded at position
    ``max_seq - 1`` (the prefill token, ``ref[0]``, is never cut by it)."""
    out = []
    for k, tok in enumerate(ref):
        out.append(tok)
        if tok == eos or len(out) >= max_new or \
                (k >= 1 and prompt_len + k >= max_seq - 1):
            return out
    raise AssertionError("the reference ends before any stop")


@pytest.mark.parametrize("arch", ["qwen2-1.5b-smoke", "mamba2-1.3b-smoke"])
def test_three_stops_in_one_step_exact(arch):
    """Three slots finish in the same decode step, each for its own reason
    (EOS, ``max_new_tokens``, the ``max_seq - 1`` cap), and each answer is
    the greedy reference cut there."""
    cfg = get_config(arch)
    params = api.init(RNG, cfg)
    M, J = 16, 3                   # all three stop at decode step J
    pa, pb = np.array([5, 6, 7, 8], np.int32), np.array([3, 1, 4], np.int32)
    pc = np.arange(20, 20 + M - 1 - J, dtype=np.int32)   # cap at step J
    ref_a = _greedy_reference(cfg, params, pa, J + 3, max_seq=M)
    ref_b = _greedy_reference(cfg, params, pb, J + 1, max_seq=M)
    ref_c = _greedy_reference(cfg, params, pc, J + 1, max_seq=M)
    eos = ref_a[J]                 # emitted mid-answer by a, by no other
    assert eos not in ref_a[:J] + ref_b + ref_c
    want = {0: _engine_cut(ref_a, eos, J + 3, len(pa), M),
            1: _engine_cut(ref_b, eos, J + 1, len(pb), M),
            2: _engine_cut(ref_c, eos, 100, len(pc), M)}
    assert [len(w) for w in want.values()] == [J + 1] * 3

    eng = Engine(cfg, params, slots=3, max_seq=M, eos_token=eos)
    for uid, (p, n) in enumerate([(pa, J + 3), (pb, J + 1), (pc, 100)]):
        eng.submit(Request(uid=uid, prompt=p, max_new_tokens=n))
    per_step = [eng.step() for _ in range(J)]
    assert [[r.uid for r in d] for d in per_step[:-1]] == [[]] * (J - 1)
    got = {r.uid: r for r in per_step[-1]}
    assert sorted(got) == [0, 1, 2] and eng.stats()["active"] == 0
    assert {u: r.output for u, r in got.items()} == want
    a, b, c = got[0], got[1], got[2]
    assert a.output[-1] == eos and len(a.output) < a.max_new_tokens
    assert eos not in b.output and len(b.output) == b.max_new_tokens
    assert eos not in c.output and len(c.output) == M - len(pc) \
        < c.max_new_tokens


@pytest.mark.parametrize("arch,scopes", [
    ("qwen2-1.5b-smoke", ("attention/kv_update", "attention", "mlp", "norm",
                          "embed", "unembed")),
    ("mamba2-1.3b-smoke", ("ssm", "norm", "embed", "unembed")),
    ("qwen3-moe-30b-a3b-smoke", ("moe", "attention/kv_update", "norm")),
])
def test_compiled_programs_named_and_scoped(arch, scopes):
    """The decode and prefill compile as jit_serve_decode and
    jit_serve_prefill, and the decode's op_name metadata carries the
    layer scopes."""
    cfg = get_config(arch)
    params = api.init(RNG, cfg)
    eng = Engine(cfg, params, slots=2, max_seq=32)
    hlo = eng._decode.lower(params, eng.cache,
                            eng.last_token).compile().as_text()
    assert hlo.startswith("HloModule jit_serve_decode")
    names = set(re.findall(r'op_name="jit\(serve_decode\)/([^"]*)"', hlo))
    for s in scopes:
        assert any(f"/{s}/" in "/" + n for n in names), s
    mini = api.init_cache(cfg, 1, 32)
    pre = eng._prefill.lower(params, mini,
                             {"tokens": jnp.zeros((1, 4), jnp.int32)})
    assert pre.as_text().startswith("module @jit_serve_prefill")
