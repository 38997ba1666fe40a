"""The trip-count-aware HLO static analyzer vs hand-computed costs — the
measurement instrument behind EXPERIMENTS.md must itself be tested."""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import hlo_analysis, roofline


def _cost(fn, *specs):
    lowered = jax.jit(fn).lower(*specs)
    return hlo_analysis.analyze(lowered.compile().as_text())


def test_plain_matmul_flops_bytes_exact():
    m, k, n = 1024, 512, 1024
    c = _cost(lambda a, b: a @ b,
              jax.ShapeDtypeStruct((m, k), jnp.float32),
              jax.ShapeDtypeStruct((k, n), jnp.float32))
    assert c.dot_flops == 2 * m * k * n
    assert c.bytes == (m * k + k * n + m * n) * 4


def test_scan_multiplies_by_trip_count():
    L = 12

    def f(x, w):
        def body(h, wl):
            return jnp.tanh(h @ wl), None
        h, _ = lax.scan(body, x, w)
        return jnp.sum(h)

    c = _cost(f, jax.ShapeDtypeStruct((8, 64), jnp.float32),
              jax.ShapeDtypeStruct((L, 64, 64), jnp.float32))
    assert c.dot_flops == L * 2 * 8 * 64 * 64
    # the built-in cost_analysis undercounts by ~L — what we're fixing
    lowered = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 64), jnp.float32),
                               jax.ShapeDtypeStruct((L, 64, 64), jnp.float32))
    builtin = lowered.compile().cost_analysis()["flops"]
    assert builtin < c.dot_flops / 4


def test_nested_scan_trip_counts_multiply():
    def f(x, w):
        def outer(h, wl):
            def inner(h2, _):
                return jnp.tanh(h2 @ wl), None
            h2, _ = lax.scan(inner, h, None, length=3)
            return h2, None
        h, _ = lax.scan(outer, x, w)
        return jnp.sum(h)

    c = _cost(f, jax.ShapeDtypeStruct((8, 32), jnp.float32),
              jax.ShapeDtypeStruct((5, 32, 32), jnp.float32))
    assert c.dot_flops == 5 * 3 * 2 * 8 * 32 * 32


def test_gather_charged_at_slice_size():
    """Embedding lookup must charge rows-read, not the whole table."""
    V, D, B = 50000, 64, 16

    def f(table, idx):
        return table[idx].sum()

    c = _cost(f, jax.ShapeDtypeStruct((V, D), jnp.float32),
              jax.ShapeDtypeStruct((B,), jnp.int32))
    # far less than one pass over the table
    assert c.bytes < V * D * 4 * 0.5


def test_dus_charged_at_update_size():
    """Decode-style KV append: charge the token write, not the cache."""
    S, D = 8192, 64

    def f(cache, x):
        def body(c, xt):
            c = lax.dynamic_update_slice(c, xt[None], (0, 0))
            return c, ()
        c, _ = lax.scan(body, cache, x)
        return c

    c = _cost(f, jax.ShapeDtypeStruct((S, D), jnp.float32),
              jax.ShapeDtypeStruct((16, D), jnp.float32))
    assert c.bytes < S * D * 4 * 4      # NOT 16 full-cache passes


def test_collective_wire_formulas():
    ops = [
        ("all-reduce", 100, 4, 2 * 100 * 3 / 4),
        ("all-gather", 100, 4, 100 * 3 / 4),
        ("reduce-scatter", 100, 4, 300),
        ("all-to-all", 100, 4, 75),
        ("collective-permute", 100, 4, 100),
    ]
    for kind, b, s, want in ops:
        got = hlo_analysis._wire_bytes(kind, b, b, s)
        assert got == want, (kind, got, want)


def test_parse_hlo_tuple_types_and_entry():
    text = """
HloModule m

%add_comp (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p: f32[8,8]) -> (f32[8,8], f32[]) {
  %p = f32[8,8] parameter(0)
  %d = f32[8,8] dot(%p, %p), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %r = f32[] reduce(%d, %p), dimensions={0,1}, to_apply=%add_comp
  ROOT %t = (f32[8,8], f32[]) tuple(%d, %r)
}
"""
    comps, entry = hlo_analysis.parse_hlo(text)
    assert entry == "main"
    model = hlo_analysis.HloCostModel(comps)
    c = model.comp_cost(entry)
    assert c.dot_flops == 2 * 8 * 8 * 8


def test_roofline_terms_math():
    t = roofline.RooflineTerms(
        flops_per_chip=197e12 * 0.5,       # 0.5 s of compute
        hbm_bytes_per_chip=819e9 * 0.25,   # 0.25 s of memory
        wire_bytes_per_chip=50e9 * 0.1,    # 0.1 s of wire
        collective_counts={},
        model_flops_per_chip=197e12 * 0.4)
    assert t.dominant == "compute"
    np.testing.assert_allclose(t.bound_s, 0.5)
    np.testing.assert_allclose(t.roofline_fraction, 0.8)
    np.testing.assert_allclose(t.useful_flops_ratio, 0.8)


# ---------------------------------------------------------------------------
# resident_bytes: the arena-footprint instrument behind the KV-format gates
# ---------------------------------------------------------------------------

def test_resident_bytes_sums_pytree_leaves():
    tree = {"k": np.zeros((2, 8, 4), np.float32),
            "v": np.zeros((2, 8, 4), np.int8),
            "s": np.zeros((2, 8), np.float32)}
    out = hlo_analysis.resident_bytes(tree)
    assert out["resident"] == 2 * 8 * 4 * 4 + 2 * 8 * 4 * 1 + 2 * 8 * 4
    # abstract leaves (eval_shape output) measure identically — footprints
    # without materialising
    abstract = jax.eval_shape(lambda: {k: jnp.asarray(v)
                                       for k, v in tree.items()})
    assert hlo_analysis.resident_bytes(abstract)["resident"] \
        == out["resident"]


def test_resident_bytes_with_compiled_memory_analysis():
    spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(lambda a, b: a @ b).lower(spec, spec).compile()
    out = hlo_analysis.resident_bytes([np.zeros((64, 64), np.float32)] * 2,
                                      compiled)
    assert out["resident"] == 2 * 64 * 64 * 4
    for key in ("argument_bytes", "output_bytes", "temp_bytes",
                "peak_bytes"):
        assert key in out and out[key] >= 0.0
    # the backend's own analysis must agree with the leaf arithmetic on
    # the declared I/O (when it reports at all — 0.0 means "not reported")
    if out["argument_bytes"]:
        assert out["argument_bytes"] == 2 * 64 * 64 * 4
    if out["output_bytes"]:
        assert out["output_bytes"] == 64 * 64 * 4
