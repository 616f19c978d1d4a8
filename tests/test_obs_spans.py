"""The span and counter store (repro.obs.spans) and the spans the trainer,
its prefetch thread and ``link.replace`` record."""

import subprocess
import sys
import threading

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.collectives.dispatch import reset_dispatcher
from repro.configs import get_smoke_config
from repro.core.runtime import PolicyRuntime
from repro.data import DataConfig
from repro.launch.train import span_summary
from repro.models.layers import MeshAxes
from repro.obs import SpanStore, spans
from repro.train import Trainer, TrainerConfig

STEP_PARTS = ["repro.data.wait", "repro.train.upload",
              "repro.train.dispatch", "repro.train.device_wait",
              "repro.train.post"]


def test_nesting_and_parent_ids():
    st = SpanStore()
    with st.span("repro.a", run=1) as a:
        assert st.current() == a.id
        with st.span("repro.b", step=3) as b:
            inner = st.snapshot()["spans"]
        with st.span("repro.c") as c:
            pass
    assert st.current() is None
    assert a.parent is None and b.parent == a.id and c.parent == a.id
    assert a.id < b.id < c.id
    assert a.start_ns <= b.start_ns <= b.end_ns <= c.start_ns <= a.end_ns
    # a span is held from the moment it opens
    assert [(s["name"], s["end_ns"]) for s in inner] == \
        [("repro.a", None), ("repro.b", None)]
    got = st.snapshot()
    assert got["dropped"] == 0
    assert [(s["name"], s["parent"], s["attrs"]) for s in got["spans"]] == \
        [("repro.a", None, {"run": 1}), ("repro.b", a.id, {"step": 3}),
         ("repro.c", a.id, {})]


def test_a_threads_spans_take_the_parent_they_are_given():
    st = SpanStore()
    seen = {}

    def work(parent):
        seen["current"] = st.current()      # stacks are per thread
        with st.span("repro.given", parent=parent) as s:
            with st.span("repro.nested") as n:
                seen["ids"] = (s.id, s.parent, n.parent, s.thread)

    with st.span("repro.run") as run:
        t = threading.Thread(target=work, args=(st.current(),))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    sid, parent, nested_parent, thread = seen["ids"]
    assert seen["current"] is None
    assert parent == run.id and nested_parent == sid
    assert thread != run.thread


def test_counters():
    st = SpanStore()
    st.count("repro.x")
    st.count("repro.x", 4)
    st.count("repro.y", 0)
    got = st.counters()
    assert got == {"repro.x": 5, "repro.y": 0}
    got["repro.x"] = 99                     # a copy
    assert st.counters()["repro.x"] == 5


def test_capacity_keeps_the_newest_and_counts_dropped():
    st = SpanStore(capacity=4)
    for i in range(10):
        with st.span("repro.s", step=i):
            pass
    got = st.snapshot()
    assert got["capacity"] == 4 and got["dropped"] == 6
    assert [s["attrs"]["step"] for s in got["spans"]] == [6, 7, 8, 9]


def test_threads_lose_no_update():
    st = SpanStore(capacity=100)
    n_threads, per = 16, 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with st.span("repro.t"):
                    st.count("repro.n")
        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    got = st.snapshot()
    assert st.counters()["repro.n"] == n_threads * per
    assert got["dropped"] + len(got["spans"]) == n_threads * per
    assert len({s["id"] for s in got["spans"]}) == len(got["spans"])


def test_spans_are_profiler_annotations(tmp_path):
    st = SpanStore()
    with st.span("repro.train.step", step_trace=True, step=7):
        pass                                # no profiler: recorded only
    jax.profiler.start_trace(str(tmp_path))
    with st.span("repro.train.step", step_trace=True, step=8):
        with st.span("repro.data.wait"):
            pass
    jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = {e.name: dict(e.stats) for p in pd.planes for ln in p.lines
             for e in ln.events if e.name.startswith("repro.")}
    assert set(names) == {"repro.train.step", "repro.data.wait"}
    assert names["repro.train.step"]["step_num"] == 8
    assert len(st.snapshot()["spans"]) == 3


@pytest.mark.parametrize("first", ["repro.obs.spans", "repro.core",
                                   "repro.obs"])
def test_import_in_any_order_without_jax(first):
    code = (f"import sys, {first}, repro.obs, repro.core.runtime; "
            "assert 'jax' not in sys.modules")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def _by_parent(held):
    kids = {}
    for s in held:
        kids.setdefault(s["parent"], []).append(s)
    return kids


def test_trainer_spans_with_a_swap_between_runs():
    from repro.policies import size_aware, static_override
    rt = PolicyRuntime()
    link = rt.attach(size_aware.program)
    reset_dispatcher(runtime=rt)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    tr = Trainer(get_smoke_config("tinyllama-1.1b"),
                 MeshAxes(tp=1, dp=1, fsdp=False), mesh,
                 TrainerConfig(steps=2, log_every=100,
                               data=DataConfig(seq_len=32, global_batch=2)))
    mark = spans.snapshot()["spans"]
    mark = mark[-1]["id"] if mark else 0
    before = spans.counters()
    tr.run(steps=2)
    link.replace(static_override.program)
    log = tr.run(steps=2)

    held = [s for s in spans.snapshot()["spans"] if s["id"] > mark]
    kids = _by_parent(held)
    tops = [s for s in held if s["parent"] is None or s["parent"] <= mark]
    assert [(s["name"], s["attrs"]) for s in tops] == [
        ("repro.train.run", {"run": 1}), ("repro.policy.replace", {}),
        ("repro.train.run", {"run": 2})]
    runs = [tops[0], tops[2]]
    steps = []
    for run in runs:
        mine = [s for s in kids[run["id"]] if s["name"] == "repro.train.step"]
        batches = [s for s in kids[run["id"]]
                   if s["name"] == "repro.data.batch"]
        assert len(mine) == 2 and len(batches) >= 2
        assert [s["attrs"]["step"] for s in batches[:2]] == \
            [s["attrs"]["step"] - 1 for s in mine]
        assert all(s["thread"] != run["thread"] for s in batches)
        steps += mine
    assert [s["attrs"]["step"] for s in steps] == [1, 2, 3, 4] == \
        [m["step"] for m in log]
    rebuilt = [s for s in held if s["name"] == "repro.train.rebuild"]
    assert len(rebuilt) == 1 and rebuilt[0]["parent"] == steps[2]["id"]
    for i, (st, m) in enumerate(zip(steps, log)):
        parts = [s["name"] for s in kids[st["id"]]]
        assert parts == (["repro.train.rebuild"] if i == 2 else []) + \
            STEP_PARTS
        ns = {s["name"]: s["end_ns"] - s["start_ns"] for s in kids[st["id"]]}
        assert sum(ns.values()) <= st["end_ns"] - st["start_ns"]
        assert m["step_time_s"] == pytest.approx(
            (ns["repro.train.dispatch"] + ns["repro.train.device_wait"])
            * 1e-9)

    # every started batch is either used or dropped when its run stops
    after = spans.counters()
    d = {k: after.get(f"repro.data.batches_{k}", 0) -
         before.get(f"repro.data.batches_{k}", 0)
         for k in ("started", "used", "dropped")}
    assert d["used"] == 4
    assert d["started"] == d["used"] + d["dropped"]
    assert d["started"] == sum(s["name"] == "repro.data.batch" for s in held)

    line = span_summary(log)
    assert line.startswith("ms per step over 2 steps: data.wait ")
    for part in ("upload", "dispatch", "device_wait", "post"):
        assert f", {part} " in line
    assert f"batches started {after['repro.data.batches_started']}, " \
           f"used {after['repro.data.batches_used']}, " \
           f"dropped {after['repro.data.batches_dropped']}" in line
