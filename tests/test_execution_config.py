"""ExecutionConfig / Session API suite: shim equivalence and deprecation.

Every graph-level entry point accepts ``config=``/``session=``; the
legacy per-call kwargs still work but warn.  The repo's own pytest config
escalates the shim's DeprecationWarning to an error
(``filterwarnings = ["error:legacy execution kwargs"]``), so these tests
double as the CI gate: any in-repo caller still on the old kwargs fails
the suite, while ``pytest.warns`` below proves the shim itself stays
functional for out-of-tree callers.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.edt import (DeviceExecutor, ExecutionConfig, Session,
                            TiledTaskGraph, synthesize, synthesize_indexed)
from repro.core.edt.config import (DEFAULT_CONFIG, UNSET, CachePolicy,
                                   resolve_execution)
from repro.core.edt.shard import process_pool
from repro.core.poly import Tiling
from repro.core.programs import PROGRAMS

PARAMS = {"N": 20}


@pytest.fixture(scope="module")
def pool():
    p = process_pool(2)
    p.submit(int, 0).result()
    yield p
    p.shutdown()


def _graph(backend="numpy"):
    return TiledTaskGraph(PROGRAMS["trisolv"](), {"S": Tiling((4, 4))},
                          backend=backend)


# ========================================================== resolution
def test_resolve_defaults():
    cfg, sess = resolve_execution(None, None)
    assert cfg is DEFAULT_CONFIG and sess is None


def test_resolve_legacy_builds_equivalent_config():
    with pytest.warns(DeprecationWarning, match="legacy execution kwargs"):
        cfg, sess = resolve_execution(
            None, None, legacy=dict(shards=3, parallel=UNSET, pool=UNSET,
                                    faults=UNSET, recovery=UNSET))
    assert sess is None
    assert cfg.shards == 3 and cfg.resolve_shards() == 3


def test_resolve_rejects_mixing():
    with pytest.raises(TypeError, match="not both"):
        resolve_execution(ExecutionConfig(), None, legacy=dict(shards=2))
    with pytest.raises(TypeError, match="not both"):
        resolve_execution(ExecutionConfig(), Session())


def test_default_call_does_not_warn():
    """Omitting every kwarg must not trip the shim (UNSET sentinel, not
    None, distinguishes "not passed")."""
    import warnings
    g = _graph()
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        ig = synthesize_indexed(g, PARAMS)[0]
    assert ig.n > 0


def test_parallel_resolves_to_cpu_count():
    import os
    assert ExecutionConfig(parallel=True).resolve_shards() == \
        (os.cpu_count() or 1)
    assert ExecutionConfig(parallel=True, shards=2).resolve_shards() == 2
    assert ExecutionConfig().resolve_shards() == 0


# ======================================================== shim warning
def test_legacy_kwargs_warn_and_match_config_results(pool):
    g = _graph()
    cfg = ExecutionConfig(shards=2, pool=pool)
    ref = g.index_graph(PARAMS, config=cfg)
    with pytest.warns(DeprecationWarning, match="legacy execution kwargs"):
        legacy = g.index_graph(PARAMS, shards=2, pool=pool)
    assert np.array_equal(legacy.edge_src, ref.edge_src)
    assert np.array_equal(legacy.edge_tgt, ref.edge_tgt)
    assert np.array_equal(legacy.pred_n, ref.pred_n)

    with pytest.warns(DeprecationWarning, match="legacy execution kwargs"):
        m = g.materialize(PARAMS, shards=2, pool=pool)
    assert m.succ == g._materialize_cfg(PARAMS, cfg).succ

    with pytest.warns(DeprecationWarning, match="legacy execution kwargs"):
        r = list(g.roots(PARAMS, shards=2, pool=pool))
    assert r == list(g.roots(PARAMS, config=cfg))

    with pytest.warns(DeprecationWarning, match="legacy execution kwargs"):
        ws = synthesize(g, PARAMS, shards=2, pool=pool)
    assert ws.levels == synthesize(g, PARAMS, config=cfg).levels

    with pytest.warns(DeprecationWarning, match="legacy execution kwargs"):
        igl, schedl = synthesize_indexed(g, PARAMS, shards=2, pool=pool)
    igc, schedc = synthesize_indexed(g, PARAMS, config=cfg)
    assert np.array_equal(schedl.level_of, schedc.level_of)

    ig = g.index_graph(PARAMS)
    with pytest.warns(DeprecationWarning, match="legacy execution kwargs"):
        run = DeviceExecutor(ig, faults=None, shards=UNSET).run()
    assert run.counters.tasks_finished == ig.n


def test_mixing_legacy_and_config_is_typeerror():
    g = _graph()
    with pytest.raises(TypeError, match="not both"):
        g.index_graph(PARAMS, shards=2, config=ExecutionConfig())
    with pytest.raises(TypeError, match="not both"):
        g.roots(PARAMS, pool=None, session=Session())


# ============================================================= session
def test_session_products_match_direct_calls():
    g = _graph()
    with Session(ExecutionConfig(backend="numpy")) as s:
        ig = s.index_graph(g, PARAMS)
        ref = g.index_graph(PARAMS)
        assert np.array_equal(ig.edge_src, ref.edge_src)
        assert list(s.roots(g, PARAMS)) == list(g.roots(PARAMS))
        assert s.synthesize(g, PARAMS).levels == synthesize(g, PARAMS).levels
        # warm: the same object comes back, and session= reuses it
        assert s.index_graph(g, PARAMS) is ig
        assert g.index_graph(PARAMS, session=s) is ig


def test_session_executor_runs_from_cached_packed():
    g = _graph()
    with Session() as s:
        run = s.executor(g, PARAMS).run()
        assert run.counters.tasks_finished == s.index_graph(g, PARAMS).n
        run2 = s.executor(g, PARAMS, replay=False).run()
        assert run2.counters.tasks_finished == run.counters.tasks_finished


def test_session_overrides_and_cache_policy():
    s = Session(cache=CachePolicy(max_entries=1))
    assert s.config.cache.max_entries == 1
    assert s.cache.policy.max_entries == 1
    s.close()


def test_session_graph_uses_configured_backend():
    with Session(ExecutionConfig(backend="fraction")) as s:
        g = s.graph(PROGRAMS["trisolv"](), {"S": Tiling((4, 4))})
        assert g.backend == "fraction"


_STOP_SCRIPT = textwrap.dedent("""
    import os

    from repro.core.edt import ExecutionConfig, Session
    from repro.core.edt.shard import stop_process_servers
    from repro.core.poly import Tiling
    from repro.core.programs import PROGRAMS

    if __name__ == "__main__":
        with Session(ExecutionConfig(backend="numpy", shards=2)) as s:
            g = s.graph(PROGRAMS["trisolv"](), {"S": Tiling((4, 4))})
            s.index_graph(g, {"N": 20})
            assert s.runtime_config().pool is not None
        pid = os.getpid()
        with open(f"/proc/{pid}/task/{pid}/children") as f:
            print("before", len(f.read().split()))
        stop_process_servers()
        with open(f"/proc/{pid}/task/{pid}/children") as f:
            print("after", len(f.read().split()))
""")


@pytest.mark.skipif(not os.path.exists("/proc/self/task"),
                    reason="reads child processes from /proc")
def test_stop_process_servers_leaves_no_child(tmp_path):
    """A closed session's pool leaves the forkserver and the resource
    tracker running; :func:`stop_process_servers` stops both, so an entry
    point returns with no process of its own still alive."""
    script = tmp_path / "entry.py"
    script.write_text(_STOP_SCRIPT)
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       os.pardir, "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    counts = dict(line.split() for line in out.stdout.splitlines())
    assert int(counts["before"]) == 2      # forkserver + resource tracker
    assert int(counts["after"]) == 0
