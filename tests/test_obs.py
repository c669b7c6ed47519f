"""``repro.obs``: spans nest and carry their save's ids across the writer
thread, the ring is bounded, records filter by time, and the compile
counter tells a compile from a persistent-cache hit."""
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np

from repro import obs
from repro.ckpt import CheckpointManager, CheckpointPolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_spans_nest_on_one_thread_and_take_counts():
    with obs.span("t.outer", step=7) as outer:
        with obs.span("t.inner") as inner:
            inner.count(bytes=10, chunks=2)
        with obs.span("t.other", step=8) as other:
            pass
    assert inner.parent == outer.id and other.parent == outer.id
    assert inner.ids == {"step": 7} and other.ids == {"step": 8}
    assert outer.start <= inner.start <= inner.end <= other.start
    assert other.end <= outer.end
    got = [s for s in obs.records() if s.id in (outer.id, inner.id)]
    assert [s.name for s in got] == ["t.inner", "t.outer"]   # by end
    assert obs.records("t.inner")[-1].counts == {"bytes": 10, "chunks": 2}


def test_parent_is_explicit_across_threads():
    seen = {}

    def job(parent):
        with obs.span("t.job", parent=parent, step=3) as s:
            with obs.span("t.job.phase") as p:
                seen.update(job=s, phase=p)

    with obs.span("t.submit") as submit:
        t = threading.Thread(target=job, args=(submit.id,))
        t.start()
        t.join()
    assert seen["job"].parent == submit.id
    assert seen["job"].thread != submit.thread
    assert seen["phase"].parent == seen["job"].id
    assert seen["phase"].ids == {"step": 3}


def test_spans_of_one_save_share_its_step(tmp_path):
    mgr = CheckpointManager(str(tmp_path), "toy",
                            CheckpointPolicy(every_steps=1, keep=2))
    params = {"w": np.arange(64 * 1024, dtype=np.float32)}
    opt = {"m": np.zeros(8, np.float32)}
    t0 = time.perf_counter()
    mgr.save(1, params, opt)
    mgr.save(2, {"w": params["w"] + 1}, opt)
    mgr.wait()
    spans = obs.records(since=t0)
    by_id = {s.id: s for s in spans}
    save = next(s for s in spans if s.name == "ckpt.save"
                and s.ids["step"] == 2)
    write = next(s for s in spans if s.name == "ckpt.write"
                 and s.parent == save.id)
    mine = [s for s in spans if s.ids.get("step") == 2]

    def under(s, root):
        while s.parent is not None and s.id != root.id:
            if s.parent not in by_id:
                return False
            s = by_id[s.parent]
        return s.id == root.id

    names = {s.name for s in mine}
    assert {"ckpt.save", "ckpt.write", "ckpt.diff", "store.inject",
            "store.write_chunks", "store.rekey", "store.commit",
            "ckpt.retention"} <= names
    assert all(s is save or under(s, save) or s.name == "ckpt.wait"
               for s in mine)
    diff = next(s for s in mine if s.name == "ckpt.diff")
    assert diff.counts["chunks_changed"] > 0
    assert diff.counts["bytes_hashed"] >= params["w"].nbytes
    assert diff.counts["hash_workers"] >= 1
    assert mgr.store.record_fingerprints
    assert diff.counts["fp_chunks"] == diff.counts["chunks_changed"]
    inject = next(s for s in mine if s.name == "store.inject")
    assert inject.parent == write.id
    assert mgr.last_report.wall_seconds == inject.seconds
    # the closing wait is on the save it waits for
    waits = [s for s in spans if s.name == "ckpt.wait"]
    assert waits[-1].ids["step"] == 2
    # a save cycle is a few dozen records, never one per chunk or leaf
    assert len(mine) < 50


def test_ring_keeps_the_newest_records():
    first = None
    for i in range(obs.RING + 10):
        with obs.span("t.ring") as s:
            first = first or s
    got = obs.records("t.ring")
    assert len(obs.records()) == obs.RING
    assert got[-1].id == s.id and first.id not in {r.id for r in got}


def test_records_filter_by_time():
    with obs.span("t.time.a") as a:
        pass
    mid = time.perf_counter()
    with obs.span("t.time.b") as b:
        pass
    assert [s.id for s in obs.records(since=mid)] == [b.id]
    assert a.id in {s.id for s in obs.records(until=mid)}
    assert b.id not in {s.id for s in obs.records(until=mid)}
    assert obs.records("t.time.a", since=a.start, until=a.end) == [a]
    totals = obs.summary(since=a.start)
    assert totals["t.time.a"]["n"] == 1 and totals["t.time.b"]["n"] == 1
    assert totals["t.time.b"]["seconds"] == b.seconds


def test_compile_counter_tells_a_compile_from_a_cache_hit(tmp_path):
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        from repro import obs

        def once():
            before = obs.compiles()
            jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(8))
            return obs.compiles() - before

        cold = once()
        jax.clear_caches()
        warm = once()
        print(cold.compiles, cold.cache_hits, warm.compiles, warm.cache_hits,
              cold.trace_s > 0, cold.lower_s > 0, warm.compile_s > 0)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    cold_n, cold_hits, warm_n, warm_hits, *positive = \
        r.stdout.split()[-7:]
    assert int(cold_n) >= 1 and int(cold_hits) == 0
    assert int(warm_n) == 0 and int(warm_hits) >= 1
    assert positive == ["True"] * 3


def test_spans_from_many_threads_are_all_kept():
    n_threads, per_thread = 32, 40
    start = threading.Barrier(n_threads)
    made = {}

    def work(i):
        start.wait(timeout=30)
        with obs.span("t.stress.root", worker=i) as root:
            for _ in range(per_thread - 1):
                with obs.span("t.stress.leaf") as leaf:
                    leaf.count(items=1)
        made[i] = root

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        t0 = time.perf_counter()
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    spans = [s for s in obs.records(since=t0) if s.name.startswith("t.stress")]
    assert len(spans) == n_threads * per_thread
    assert len({s.id for s in spans}) == len(spans)
    roots = {s.id: s.ids["worker"] for s in spans if s.name == "t.stress.root"}
    for s in spans:
        if s.name == "t.stress.leaf":
            assert made[s.ids["worker"]].id == s.parent and s.parent in roots
    leaves = obs.summary(since=t0)["t.stress.leaf"]
    assert leaves["n"] == leaves["items"] == n_threads * (per_thread - 1)
