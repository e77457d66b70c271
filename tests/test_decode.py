"""Decode-path correctness: step-by-step decode with caches must reproduce
the full-forward logits — this exercises KV caches (incl. the sliding-window
ring buffer), SSM states, RG-LRU states and enc-dec cross attention."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model

KEY = jax.random.PRNGKey(1)
# one representative per cache mechanism
ARCHS = ["qwen2.5-32b",          # plain KV
         "gemma3-27b",           # window ring buffer + sandwich norms
         "mamba2-130m",          # SSD state
         "recurrentgemma-9b",    # RG-LRU + window MQA
         "whisper-small"]        # enc-dec cross attention


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch, mesh1):
    cfg = get_config(arch, smoke=True)
    model = make_model(cfg)
    params = model.init(KEY)
    b, s = 2, 24
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    if cfg.arch_type == "encdec":
        enc = jax.random.normal(KEY, (b, cfg.enc_seq, cfg.d_model))
        full_logits, _ = model.apply(params, enc, toks, remat=False)
        memory = model.encode(params, enc, remat=False)
        caches = model.init_caches(b, s)
        outs = []
        for i in range(s):
            lg, caches = model.decode_step(params, toks[:, i:i + 1], caches,
                                           jnp.int32(i), memory)
            outs.append(lg)
    else:
        full_logits, _ = model.apply(params, toks, remat=False)
        caches = model.init_caches(b, s)
        outs = []
        for i in range(s):
            lg, caches = model.decode_step(params, toks[:, i:i + 1], caches,
                                           jnp.int32(i))
            outs.append(lg)
    dec_logits = jnp.concatenate(outs, axis=1)
    a = jax.nn.log_softmax(full_logits.astype(jnp.float32), axis=-1)
    c = jax.nn.log_softmax(dec_logits.astype(jnp.float32), axis=-1)
    err = float(jnp.max(jnp.abs(a - c)))
    # recurrence archs accumulate bf16 order-of-operations noise between
    # the chunk-parallel and strictly-sequential paths; attention archs
    # recompute identically. Greedy decisions must agree in all cases.
    tol = 5e-2 if cfg.family in ("dense", "audio", "vlm") else 1.5
    assert err < tol, f"{arch}: decode/forward divergence {err}"
    agree = float((a.argmax(-1) == c.argmax(-1)).mean())
    assert agree > 0.95, f"{arch}: greedy tokens diverge ({agree})"


def _greedy_loop(model, params, prompt, n_new):
    """Greedy decoding as a plain loop over ``model.decode_step``: the
    tokens and every step's logits."""
    step = jax.jit(model.decode_step)
    b, plen = prompt.shape
    caches = model.init_caches(b, plen + n_new)
    toks, logits = [prompt], []
    tok = None
    for i in range(plen + n_new - 1):
        inp = prompt[:, i:i + 1] if i < plen else tok
        lg, caches = step(params, inp, caches, jnp.int32(i))
        logits.append(lg)
        if i >= plen - 1:
            tok = jnp.argmax(lg[:, -1:], axis=-1).astype(jnp.int32)
            toks.append(tok)
    return jnp.concatenate(toks, axis=1), jnp.concatenate(logits, axis=1)


# qwen1.5: QKV biases, untied float32 head; gemma3: zero-centered norms
# and QK norms (kept float32 in the serving copy), tied head, window ring;
# deepseek-v2: latent attention and its norms, a leading dense layer,
# held experts with their float32 router, the step's counters.
@pytest.mark.parametrize("arch", ["qwen1.5-4b", "gemma3-27b",
                                  "deepseek-v2-236b-a21b"])
def test_generate_engine(arch, mesh1):
    from repro.serving import Engine
    cfg = get_config(arch, smoke=True)
    model = make_model(cfg)
    params = model.init(KEY)
    eng = Engine(model, cfg, max_len=64)
    prompt = jax.random.randint(KEY, (2, 5), 0, cfg.vocab)
    out = eng.generate(params, prompt, n_new=8)
    assert out.shape == (2, 13)
    assert bool((out[:, :5] == prompt).all())
    # The engine serves from its cast copy; the plain loop from the raw
    # float32 params.  Same tokens, and the same logits along them.
    want, want_logits = _greedy_loop(model, params, prompt, 8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    served = model.serving_params(params)
    caches = model.init_caches(2, 64)
    got = []
    for i in range(out.shape[1] - 1):
        lg, caches = eng._step(served, out[:, i:i + 1], caches, jnp.int32(i))
        got.append(lg)
    got = jnp.concatenate(got, axis=1)
    assert got.dtype == want_logits.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want_logits))) < 1e-3


def test_decode_program_converts_no_weight(mesh1):
    # The engine's decode program, lowered on what it serves from, converts
    # no embedding table and no layer-stack weight or bias: neither stacked
    # (as XLA hoists a cast out of the layer scan) nor one layer's slice.
    # The norm scales stay float32 and are cast in the step.
    from repro.serving import Engine
    cfg = get_config("qwen1.5-4b", smoke=True)
    model = make_model(cfg)
    params = model.init(KEY)
    shapes = {params["embed"]["embedding"].shape}
    for path, a in jax.tree_util.tree_flatten_with_path(params["stack"])[0]:
        if path[-1].key != "scale":
            shapes |= {a.shape, a.shape[1:]}
    eng = Engine(model, cfg, max_len=32)
    text = eng._step.lower(model.serving_params(params),
                           jnp.zeros((2, 1), jnp.int32),
                           model.init_caches(2, 32), jnp.int32(0)).as_text()
    converted = {tuple(int(d) for d in dims.split("x")) for dims in
                 re.findall(r"stablehlo\.convert %\S+ : \(tensor<([0-9x]+)x\w+>",
                            text)}
    assert converted, "no convert read: the pattern misses the module's text"
    assert not converted & shapes, sorted(converted & shapes)


def test_serving_copy_built_once(tmp_path, mesh1):
    from bench.core import spans, trace
    from repro.serving import Engine
    cfg = get_config("qwen1.5-4b", smoke=True)
    model = make_model(cfg)
    params = model.init(KEY)
    prompt = jax.random.randint(KEY, (2, 4), 0, cfg.vocab)
    eng = Engine(model, cfg, max_len=32)
    with jax.profiler.trace(str(tmp_path), profiler_options=trace.options()):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            first = np.asarray(eng.generate(params, prompt, 3))
            second = np.asarray(eng.generate(params, prompt, 3))
    np.testing.assert_array_equal(first, second)
    tel = eng.transfer_telemetry()
    assert (tel["weight_preparations"], tel["weight_reuses"]) == (1, 1)
    table = spans.reduce(trace.find_xplane(tmp_path)).span_table()
    assert table["engine.prepare_weights"][0] == 1
    assert table["engine.generate"][0] == 2
    # New arrays build a new copy, which replaces the held one.
    other = jax.tree.map(lambda a: a + 0, params)
    np.testing.assert_array_equal(
        np.asarray(eng.generate(other, prompt, 3)), first)
    tel = eng.transfer_telemetry()
    assert (tel["weight_preparations"], tel["weight_reuses"]) == (2, 1)
    eng.generate(params, prompt, 3)
    assert eng.transfer_telemetry()["weight_preparations"] == 3
