"""The attribution of a traced serving run to the program's spans, programs
and scopes (``tracing.reduce``), on a hand-built trace; the readers of its
per-layer numbers on a hand-built record; and a traced run of a tiny cell
on the CPU."""
import gc
import json
import pathlib
import sys
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import chipbench_tiny  # noqa: E402
from benchmarks.chip import common, tracing  # noqa: E402
from repro.serve import Request  # noqa: E402

HLO = """HloModule jit_serve_decode, is_scheduled=true

ENTRY %main.9 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0), metadata={op_name="t"}
  %while.16 = (s32[], bf16[8]) while(%t), condition=%c, body=%b, \
metadata={op_name="jit(serve_decode)/while" stack_frame_id=1}
  %fusion.145 = bf16[32]{0} fusion(%x), kind=kOutput, calls=%f, \
metadata={op_name="jit(serve_decode)/while/body/closed_call/attention/\
bkgst,btkh->bskgh/dot_general" stack_frame_id=2}
  %dynamic-slice.5 = bf16[8]{0} dynamic-slice(%y), \
metadata={op_name="jit(serve_decode)/while/body/squeeze"}
  %while.17 = (s32[], bf16[8]) while(%u), condition=%c2, body=%b2, \
metadata={op_name="jit(serve_decode)/while/body/closed_call/attention/\
kv_update/vmap(vmap())/scatter"}
  %scatter.3 = bf16[8]{0} scatter(%v), metadata={op_name="jit(serve_decode)\
/while/body/closed_call/attention/kv_update/vmap(vmap())/scatter"}
  %fusion.1 = bf16[8]{0} fusion(%w), kind=kLoop, calls=%g, \
metadata={op_name="jit(serve_decode)/while/body/closed_call/mlp/jit(silu)/mul"}
  ROOT %copy.2 = bf16[8]{0} copy(%z)
}
""".replace("\\\n", "")


def trace(program: bool = True) -> tracing.Trace:
    """Traced span 10..20 s on one chip.  A step 10..16: admission (one
    prefill, one splice), decode, retire (a collection inside it); then the
    generator waits, and the next batch is read.  The device runs the
    prefill, an eager splice and the decode, whose layer loop holds
    attention, the cache slice, the row update loop (with an instruction of
    its own body that has no op_name) and an mlp op named as the prefill's
    op is."""
    ops = [("%fusion.1 bf16[8]", 10.0, 10.8),                 # prefill's
           ("%scatter.1 bf16[8]", 11.2, 11.6),                # eager splice
           ("%while.16", 12.2, 14.2),
           ("%fusion.145 bf16[32]", 12.4, 12.8),
           ("%dynamic-slice.5 bf16[8]", 12.8, 13.6),
           ("%while.17", 13.6, 14.0),
           ("%fusion.148 s32[4]", 13.62, 13.68),        # no op_name
           ("%scatter.3 bf16[8]", 13.7, 13.9),
           ("%fusion.1 bf16[8]", 14.0, 14.2)]
    modules = [("jit_serve_prefill", 10.0, 10.8), ("jit_scatter", 11.2, 11.6),
               ("jit_serve_decode", 12.2, 14.2)]
    spans = [("traced", 10.0, 20.0, {}), ("engine.step", 10.0, 16.0, {}),
             ("generator wait", 16.0, 18.0, {}), ("next batch", 18.0, 19.0, {})]
    if program:
        spans += [("serve.admit", 10.0, 12.0, {}),
                  ("serve.prefill", 10.0, 11.0, {"uid": 5, "prompt_len": 256}),
                  ("serve.splice", 11.0, 11.5, {"uid": 5}),
                  ("serve.decode", 12.0, 12.5, {"batch": 2}),
                  ("serve.retire", 12.5, 16.0, {}),
                  ("host.gc", 13.0, 13.5, {"generation": 2})]
    return tracing.Trace(ops={0: ops}, spans=spans,
                         modules={0: modules if program else []})


# the keys the reduction had before it read the program's spans, programs
# and scopes
BENCHMARK_KEYS = ("op_count", "window_s", "steps", "busy_s", "idle_share",
                  "collective_exposed_s", "device_ops", "idle_gaps",
                  "ops_from_to_s", "span_s")


def test_benchmark_keys_unchanged_by_program_spans():
    """tracing.reduce's driver-span keys read the same with and without the
    program's spans and module lines; only its idle-gap labels name the
    innermost span."""
    bare = tracing.reduce(trace(program=False), [0])
    full = tracing.reduce(trace(), [0])
    assert bare.keys() == full.keys() >= set(BENCHMARK_KEYS)
    for k in BENCHMARK_KEYS:
        if k != "idle_gaps":
            assert bare[k] == full[k], k
    assert [d for _, d in bare["idle_gaps"]] == \
        [d for _, d in full["idle_gaps"]]
    assert full["steps"] == 1          # engine.step alone counts steps
    assert not set(tracing.SPANS) & {s[0] for s in trace().spans
                                     if s[0].startswith("serve.")}
    # the gap 11.6-12.2 s lies in the admission, inside the step
    assert [l for l, d in full["idle_gaps"] if abs(d - 0.6) < 1e-9] == \
        ["serve.admit at 1.60 s"]
    assert [l for l, d in bare["idle_gaps"] if abs(d - 0.6) < 1e-9] == \
        ["engine.step at 1.60 s"]


def test_reduce_spans_idle_modules():
    s = tracing.reduce(trace(), [0])
    assert s["span_self_s"] == pytest.approx({
        "engine.step": 0.0, "serve.admit": 0.5, "serve.prefill": 1.0,
        "serve.splice": 0.5, "serve.decode": 0.5, "serve.retire": 3.0,
        "host.gc": 0.5, "generator wait": 2.0, "next batch": 1.0})
    assert s["span_total_s"]["serve.admit"] == pytest.approx(2.0)
    assert s["span_total_s"]["serve.retire"] == pytest.approx(3.5)
    assert set(s["span_n"].values()) == {1}
    # the chip idles 10.8-11.2, 11.6-12.2 and 14.2-20: each piece is owned
    # by the innermost span open, and the split is whole
    assert s["idle_by_span_s"] == pytest.approx({
        "serve.prefill": 0.2, "serve.splice": 0.2, "serve.admit": 0.4,
        "serve.decode": 0.2, "serve.retire": 1.8, "generator wait": 2.0,
        "next batch": 1.0, "no span": 1.0})
    busy = s["busy_s"]
    assert sum(s["idle_by_span_s"].values()) == pytest.approx(10.0 - busy)
    assert s["module_s"] == pytest.approx({
        "jit_serve_prefill": 0.8, "jit_scatter": 0.4, "jit_serve_decode": 2.0})
    assert sum(s["module_s"].values()) == pytest.approx(busy)
    assert s["scope_s"] == {}                   # no decode HLO given


def test_scope_self_time_over_nested_while():
    """Self time inside the decode's intervals only, by the scope of each
    instruction: a while counts the time its body's ops leave, an op with
    no op_name takes its loop's scope, the prefill's op of the same name
    does not count, and the scopes add up to the decode's device time."""
    scopes = tracing.scopes_from_hlo(HLO)
    assert scopes["%while.16"] == "(no scope)"
    assert scopes["%fusion.145"] == "attention"
    assert scopes["%while.17"] == scopes["%scatter.3"] == "attention/kv_update"
    assert scopes["%fusion.1"] == "mlp" and "%copy.2" not in scopes
    assert "%fusion.148" not in scopes
    s = tracing.reduce(trace(), [0], scopes)
    assert s["scope_s"] == pytest.approx({
        "(no scope)": 0.2 + 0.8, "attention": 0.4,
        "attention/kv_update": 0.14 + 0.06 + 0.2, "mlp": 0.2})
    assert sum(s["scope_s"].values()) == \
        pytest.approx(s["module_s"]["jit_serve_decode"])


def test_scopes_of_compiled_decode():
    """On a decode compiled here: the named scopes, and JAX's own parts
    left out."""
    assert tracing.scope_of(
        "jit(serve_decode)/while/body/closed_call/attention/kv_update/"
        "vmap(vmap())/scatter") == "attention/kv_update"
    assert tracing.scope_of("jit(serve_decode)/while/body/squeeze") == \
        "(no scope)"
    assert tracing.scope_of("jit(serve_decode)/embed/jit(_take)/lt") == \
        "embed"
    assert tracing.scope_of(
        "jit(serve_prefill)/while/body/closed_call/ssm/transpose;"
        "ssm/bcqn,bcqh->bchnp/transpose") == "ssm"
    import jax
    from repro.models import api, get_config
    from repro.serve import Engine
    cfg = get_config("qwen2-1.5b-smoke")
    params = api.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, slots=2, max_seq=32)
    hlo = eng._decode.lower(params, eng.cache,
                            eng.last_token).compile().as_text()
    found = set(tracing.scopes_from_hlo(hlo).values())
    assert {"attention", "attention/kv_update", "mlp", "norm", "embed",
            "unembed", "(no scope)"} <= found


def test_gc_spans_time_collections():
    with tracing.GcSpans() as g:
        gc.collect()
    assert g._callback not in gc.callbacks
    s = g.summary(float("-inf"), float("inf"))
    assert s["collections"] >= 1 and s["by_generation"][2] >= 1
    assert 0 < s["longest_ms"] <= s["total_ms"]
    assert g.summary(0.0, 0.0)["collections"] == 0


# --------------------------------------------------------------------------
# readers
# --------------------------------------------------------------------------

def record():
    """The hand-built trace's reduction, counters of four decode steps, and
    four requests of the window: two admitted, one waiting, one refused."""
    s = tracing.reduce(trace(), [0], tracing.scopes_from_hlo(HLO))

    def req(t_submit, t_admit, rejected=False):
        r = Request(uid=0, prompt=None, rejected=rejected)
        r.t_submit, r.t_admit = t_submit, t_admit
        return types.SimpleNamespace(req=r)
    recs = [req(1.0, 1.01), req(2.0, 2.2), req(3.0, None),
            req(4.0, None, rejected=True), types.SimpleNamespace(req=None)]
    return types.SimpleNamespace(
        trace=s, window=types.SimpleNamespace(recs=recs, t_stop=3.5),
        engine={"decode_steps": 4, "slot_steps": 6, "host_syncs": 13,
                "prefills": 1})


EXPECTED = {
    # waits 10, 200 and (3.5 - 3.0) = 500 ms
    "engine_queue_wait_p95_ms": common.percentile([10.0, 200.0, 500.0], 95),
    "admit_ms_per_prefill.serve": 2000.0,
    "prefill_dev_ms.serve": 800.0,
    "decode_dev_ms_per_step.serve": 2000.0,
    "retire_ms_per_step.serve": 3000.0,
    "host_syncs_per_step.serve": 13 / 4,
    "batch_per_step.serve": 6 / 4,
    "attention_dev_ms_per_step.decode": 800.0,
    # the layer loop's 0.2 s and the cache slice's 0.8 s lie in no scope
    "stack_dev_ms_per_step.decode": 1000.0,
}
READERS = tuple(EXPECTED)


def reader(name):
    return common.load_module(REPO / "benchmarks" / "chip" / "metrics"
                              / f"{name}.py")


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_built_record(name):
    """Every listed metric that reads the program's spans or counters is
    checked here, and each reader here is listed."""
    listed = {m["name"]: m["source"] for m in json.loads(
        (REPO / "BENCHMARK.json").read_text())["per_layer"]}
    assert set(EXPECTED) <= set(listed)
    assert {k for k, v in listed.items()
            if v in ("program_span", "program_counter")} <= set(EXPECTED)
    assert reader(name).read(record()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing(name):
    """A record from a program without the spans, programs, counters and
    stamps, as `drivers/serve.py` records it: None, never 0."""
    bare = tracing.reduce(trace(program=False), [0])
    old = types.SimpleNamespace(
        trace=bare, window=types.SimpleNamespace(
            recs=[types.SimpleNamespace(req=types.SimpleNamespace(
                rejected=False, t_admit=None))], t_stop=1.0))
    assert reader(name).read(old) is None
    empty = types.SimpleNamespace(
        trace=None, engine={}, window=types.SimpleNamespace(recs=[], t_stop=1))
    assert reader(name).read(empty) is None


# --------------------------------------------------------------------------
# a traced run of a tiny cell
# --------------------------------------------------------------------------

def test_tool_on_tiny_cell(monkeypatch, tmp_path, capsys):
    """run.py --trace 1 reads the program's spans and counters: its stderr
    ``trace`` line holds the whole reduction, the engine's counters over the
    traced span, the window's collections and the decode's HLO time."""
    root = chipbench_tiny.make_root(tmp_path)
    monkeypatch.setattr(common, "HERE", root)
    monkeypatch.setattr(common, "ROOT", tmp_path)
    monkeypatch.setattr(common, "PLATFORM", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    run = common.load_module(REPO / "benchmarks" / "chip" / "run.py")
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    rc = run.main(["--workload", "tiny-qwen2.chat", "--seed", "3000000001",
                   "--seconds", "2", "--trace", "1"])
    assert rc == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    notes = dict(l.split(": ", 1) for l in err.splitlines() if ": {" in l)
    t = json.loads(notes["trace"])
    m, e = line["metrics"], t["engine"]
    assert json.loads(notes["trace_events"])["device_ops"] == t["op_count"]
    # the CPU has no device plane: device numbers are missing, not 0
    for name in ("prefill_dev_ms.serve", "decode_dev_ms_per_step.serve",
                 "attention_dev_ms_per_step.decode",
                 "stack_dev_ms_per_step.decode"):
        assert name not in m
    assert m["engine_queue_wait_p95_ms"]["value"] >= 0
    assert m["batch_per_step.serve"]["value"] == pytest.approx(
        e["slot_steps"] / e["decode_steps"])
    # one read per decode step, whatever the batch, and one per admission
    assert e["host_syncs"] == e["decode_steps"] + e["prefills"]
    assert m["host_syncs_per_step.serve"]["value"] == pytest.approx(
        e["host_syncs"] / e["decode_steps"])
    assert t["span_n"]["serve.decode"] == e["decode_steps"] == t["steps"]
    assert t["span_n"]["serve.prefill"] == e["prefills"]
    assert m["retire_ms_per_step.serve"]["value"] > 0
    assert sum(t["idle_by_span_s"].values()) == pytest.approx(t["window_s"])
    assert t["gc"]["collections"] >= 0 and t["decode_hlo_s"] > 0
    assert t["module_s"] == {} and t["scope_s"] == {}
    assert line["correct"] is True
