"""The program's host spans (``nom.*`` in the allocator, ``engine.*`` in
the serving Engine), recorded under ``jax.profiler.trace`` on the CPU and
reduced by the benchmark's reducer: one span per unit of work the reports
count, and results bit-identical traced and untraced."""
import jax
import numpy as np
import pytest

from bench.core import harness, spans, trace
from repro.core.slot_alloc import CopyRequest, TdmAllocator
from repro.core.topology import Mesh3D


def _traced(tmp_path, fn):
    """Run ``fn`` inside a window span under the profiler; return its
    result and the reduced trace."""
    with jax.profiler.trace(str(tmp_path), profiler_options=trace.options()):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            out = fn()
    return out, spans.reduce(trace.find_xplane(tmp_path))


def _contended(mesh, n, seed):
    """Copies funnelled through one mesh column, so that commits of one
    wave conflict and re-search."""
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n):
        s = mesh.node_id(0, int(rng.integers(mesh.Y)), 0)
        d = mesh.node_id(mesh.X - 1, int(rng.integers(mesh.Y)),
                         int(rng.integers(mesh.Z)))
        reqs.append(CopyRequest(s, d, int(rng.integers(1, 4096))))
    return reqs


def _key(res):
    c = res.circuit
    return None if c is None else (c.src, c.dst, c.start_cycle, c.n_windows,
                                   tuple(c.hops), c.distance)


@pytest.mark.parametrize("backend", ["fused", "auto"])
def test_allocator_spans_count_the_report(tmp_path, backend):
    mesh = Mesh3D(4, 4, 2, vault_span_y=1)
    reqs = _contended(mesh, 48, seed=3)
    plain = TdmAllocator(mesh, 8, backend=backend)
    plain.search_wave = 16
    want = [_key(r) for r in plain.allocate_batch(reqs, cycle=0)]
    alloc = TdmAllocator(mesh, 8, backend=backend)
    alloc.search_wave = 16
    alloc.allocate_batch(reqs[:16], cycle=0)      # compile outside the trace
    alloc = TdmAllocator(mesh, 8, backend=backend)
    alloc.search_wave = 16
    got, r = _traced(tmp_path, lambda: alloc.allocate_batch(reqs, cycle=0))
    rep = alloc.last_report
    assert [_key(x) for x in got] == want
    assert np.array_equal(alloc.table.expiry, plain.table.expiry)
    assert rep.conflicts > 0 and rep.fused_waves > 0
    table = r.span_table()
    assert table["nom.alloc.batch"][0] == 1
    assert table["nom.alloc.research"][0] == rep.conflicts
    # One launch and one wait per program run: the waves', and on the
    # fused backend the re-searches' too.
    assert table["nom.alloc.fused.dispatch"][0] == rep.fused_waves
    assert table["nom.alloc.fused.wait"][0] == rep.fused_waves
    # Every wave of 16 rides the fused fast path: one commit and emission
    # each; no wave takes the general path.
    for name in ("nom.alloc.fused.commit", "nom.alloc.fused.emit"):
        assert table[name][0] == 3, name
    assert "nom.alloc.wave" not in table
    # The batch's self times add up to the batch.
    batch_ms = table["nom.alloc.batch"][1]
    assert sum(own for name, (_, _, own) in table.items()
               if name.startswith("nom.alloc")) == pytest.approx(batch_ms)


def test_allocator_host_waves_are_spanned(tmp_path):
    mesh = Mesh3D(4, 4, 2, vault_span_y=1)
    reqs = _contended(mesh, 24, seed=5)
    plain = TdmAllocator(mesh, 8, backend="host")
    want = [_key(r) for r in plain.allocate_batch(reqs, cycle=0)]
    alloc = TdmAllocator(mesh, 8, backend="host")
    got, r = _traced(tmp_path, lambda: alloc.allocate_batch(reqs, cycle=0))
    rep = alloc.last_report
    assert [_key(x) for x in got] == want
    table = r.span_table()
    assert table["nom.alloc.wave"][0] == rep.host_waves - rep.conflicts
    assert table.get("nom.alloc.research", [0])[0] == rep.conflicts
    assert "nom.alloc.fused.wait" not in table


def test_waves_with_an_init_take_the_general_path_fused(tmp_path):
    # An INIT in a wave keeps it off the fused fast path: the wave is
    # prepared by the fused program and committed state by state.
    mesh = Mesh3D(4, 4, 2, vault_span_y=1)
    reqs = _contended(mesh, 32, seed=7)
    reqs[3] = CopyRequest(reqs[3].src, reqs[3].src, 4096, op="init")
    reqs[20] = CopyRequest(reqs[20].src, reqs[20].src, 4096, op="init")
    plain = TdmAllocator(mesh, 8, backend="fused")
    plain.search_wave = 16
    want = [_key(r) for r in plain.allocate_batch(reqs, cycle=0)]
    alloc = TdmAllocator(mesh, 8, backend="fused")
    alloc.search_wave = 16
    got, r = _traced(tmp_path, lambda: alloc.allocate_batch(reqs, cycle=0))
    rep = alloc.last_report
    assert [_key(x) for x in got] == want
    table = r.span_table()
    assert table["nom.alloc.wave"][0] == 2
    assert "nom.alloc.fused.commit" not in table
    assert table["nom.alloc.fused.dispatch"][0] == rep.fused_waves
    assert table["nom.alloc.fused.wait"][0] == rep.fused_waves
    assert table.get("nom.alloc.research", [0])[0] == rep.conflicts


def test_engine_spans_one_tick_per_model_step(tmp_path, mesh1):
    from repro.configs import get_config
    from repro.models import make_model
    from repro.serving import Engine
    cfg = get_config("qwen1.5-4b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (2, 5), 0,
                                           cfg.vocab))
    n_new = 4
    plain = np.asarray(Engine(model, cfg, max_len=32).generate(
        params, prompt, n_new, tenant="a"))
    eng = Engine(model, cfg, max_len=32)
    out, r = _traced(tmp_path, lambda: np.asarray(
        eng.generate(params, prompt, n_new, tenant="a")))
    assert np.array_equal(out, plain)
    table = r.span_table()
    assert table["engine.schedule_tick"][0] == prompt.shape[1] + n_new - 1
    for name in ("engine.generate", "engine.prefill", "engine.open_tenant",
                 "engine.close_tenant"):
        assert table[name][0] == 1, name
    # Every tick nests the fabric's session call.
    assert table["nom.schedule"][0] >= table["engine.schedule_tick"][0]
    (gen,) = r.in_window("engine.generate")
    assert gen.args == {"tenant": "a"}
    (first,) = r.first_tokens()
    assert first["tenant"] == "a" and first["prompt_len"] == prompt.shape[1]
    assert 0 < first["ttft_s"] <= (gen.end - gen.start) * 1e-9
    # The serve cell's span metrics read this trace.
    ctx = harness.ReadContext(trace=r, counters={}, work={}, window=None,
                              device_kind="cpu", chips=1)
    total, _, ticks = r.span_ms("engine.schedule_tick")
    assert harness.load_reader("control_plane_ms.serve")(ctx) == \
        pytest.approx(total / ticks)
    assert harness.load_reader("ttft_ms.serve")(ctx) == \
        pytest.approx(1e3 * first["ttft_s"])
    assert harness.load_reader("ttft_per_token_ms.serve")(ctx) == \
        pytest.approx(1e3 * first["ttft_s"] / prompt.shape[1])
