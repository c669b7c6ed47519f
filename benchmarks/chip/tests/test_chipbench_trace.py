"""The trace reduction on a small trace recorded on the CPU."""
import time

import jax
import jax.numpy as jnp

from chipbench_smoke import BENCH  # noqa: F401
from chipbench.trace import (SPAN_PREFIX, WINDOW_SPAN, find_xplane,
                             reduce_trace, union)


def cpu_selectors():
    return dict(device_plane=lambda n: n == "/host:CPU",
                op_line=lambda n: n.startswith("tf_XLA"),
                module_line=lambda n: False)


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduction_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        for _ in range(3):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "host_wait"):
            time.sleep(0.2)
    jax.profiler.stop_trace()
    s = reduce_trace(find_xplane(str(tmp_path)), **cpu_selectors())
    assert s is not None and s.devices == 1
    assert 0.2 <= s.window_s < 30
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    # the sleep is a device gap inside the span that was open during it
    assert s.gap_s.get(SPAN_PREFIX + "host_wait", 0) > 0.15
    assert abs(sum(s.gap_s.values()) - (s.window_s - s.busy_s)) < 1e-6
    assert s.top_ops(3) and s.top_ops(3)[0][1] > 0
    assert s.spans[WINDOW_SPAN][0] == s.window_s


def test_no_device_operations_gives_nothing(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    time.sleep(0.01)
    jax.profiler.stop_trace()
    assert reduce_trace(find_xplane(str(tmp_path)),
                        device_plane=lambda n: False) is None
