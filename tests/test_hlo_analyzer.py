"""HLO roofline analyzer: trip-count-exact flops, touched-rows byte model.

These tests also document WHY the analyzer exists: XLA's cost_analysis counts
while bodies once and charges gathers their full operand.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analyzer import analyze_hlo


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_body_multiplied_by_trip_count():
    w = jnp.ones((8, 128, 128))

    def f(x):
        y, _ = jax.lax.scan(lambda c, wi: (c @ wi, None), x, w)
        return y.sum()

    comp = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    c = analyze_hlo(comp.as_text())
    expect = 8 * 2 * 128**3
    assert 0.8 * expect < c.flops < 1.3 * expect
    # and XLA's own analysis indeed counts the body once (the motivation)
    ca = comp.cost_analysis()
    assert ca["flops"] < 0.3 * expect


def test_gather_charges_touched_rows_not_table():
    t = jnp.ones((1_000_000, 64))
    idx = jnp.arange(1000, dtype=jnp.int32)
    comp = _compile(lambda t, i: jnp.take(t, i, axis=0), t, idx)
    c = analyze_hlo(comp.as_text())
    touched = 2 * 1000 * 64 * 4 + 1000 * 4
    assert c.bytes < 4 * touched  # not 256 MB
    assert c.bytes >= 0.5 * touched


def test_donated_scatter_charges_updates():
    t = jnp.ones((1_000_000, 64))
    idx = jnp.arange(1000, dtype=jnp.int32)
    u = jnp.ones((1000, 64))
    comp = jax.jit(lambda t, i, u: t.at[i].set(u), donate_argnums=(0,)).lower(t, idx, u).compile()
    c = analyze_hlo(comp.as_text())
    assert c.bytes < 8e6  # not 0.5 GB


def test_matmul_flops_including_onednn_custom_call():
    comp = _compile(lambda a, b: a @ b, jnp.ones((256, 512)), jnp.ones((512, 128)))
    c = analyze_hlo(comp.as_text())
    expect = 2 * 256 * 512 * 128
    assert 0.9 * expect < c.flops < 1.2 * expect


def test_batched_einsum_flops():
    comp = _compile(
        lambda a, b: jnp.einsum("bik,bkj->bij", a, b),
        jnp.ones((4, 64, 32), jnp.bfloat16), jnp.ones((4, 32, 16), jnp.bfloat16),
    )
    c = analyze_hlo(comp.as_text())
    expect = 2 * 4 * 64 * 32 * 16
    assert 0.8 * expect < c.flops < 1.5 * expect


# -- edge cases: the HLO shapes that broke (or nearly broke) the parser ------


def test_rolled_while_loop_scatter_stays_touched_rows():
    """XLA lowers a donated per-row update loop to a rolled `while` whose body
    dynamic-slices one row and dynamic-update-slices it back.  The donated
    table param is consumed only through that loop — it must be charged at
    touched-rows size, not once-per-trip x full table (16 MB x 64 trips)."""
    t = jnp.ones((65536, 64))

    def f(t, u):
        def body(i, acc):
            return acc.at[i * 7].set(u[i])

        return jax.lax.fori_loop(0, 64, body, t)

    comp = jax.jit(f, donate_argnums=(0,)).lower(t, jnp.ones((64, 64))).compile()
    c = analyze_hlo(comp.as_text())
    # loose: well under one full-table sweep (16.7 MB); the real traffic is
    # 64 rows in + RMW out, a few hundred KB
    assert c.bytes < t.size * t.dtype.itemsize
    assert c.bytes > 0


def test_cost_analysis_list_return_is_normalized_by_tests():
    """``cost_analysis()`` returns one dict of XLA's counts (the form
    ``roofline`` reads under ``xla_raw``)."""
    comp = _compile(lambda a, b: a @ b, jnp.ones((64, 64)), jnp.ones((64, 64)))
    ca = comp.cost_analysis()
    assert isinstance(ca, dict) and "flops" in ca
    assert ca["flops"] > 0


def test_multi_computation_module_parses_every_computation():
    """scan + cond + custom_vjp in one program: the module text carries many
    non-entry computations (while body/condition, branch computations, fused
    subgraphs).  parse_computations must find them all and identify ENTRY."""
    from repro.launch.hlo_analyzer import parse_computations

    @jax.custom_vjp
    def sq(x):
        return x * x

    sq.defvjp(lambda x: (x * x, x), lambda x, g: (2.0 * x * g,))

    def loss(x, w):
        def step(c, wi):
            c = jax.lax.cond(c.sum() > 0, lambda v: v @ wi, lambda v: v - 1.0, c)
            return c, None

        y, _ = jax.lax.scan(step, sq(x), w)
        return y.sum()

    comp = _compile(jax.grad(loss), jnp.ones((16, 16)), jnp.ones((4, 16, 16)))
    hlo = comp.as_text()
    comps, entry = parse_computations(hlo)
    assert entry is not None and entry in comps
    assert len(comps) > 1, "while/cond bodies must parse as separate computations"
    # every instruction name defined in a computation has a parsed type
    for c in comps.values():
        for ins in c.instrs:
            assert ins.name in c.types
    # and the analyzer still walks it end-to-end with sane totals
    r = analyze_hlo(hlo)
    assert r.flops > 0 and r.bytes > 0


def test_empty_and_headerless_text_do_not_crash():
    from repro.launch.hlo_analyzer import parse_computations

    comps, entry = parse_computations("")
    assert comps == {} and entry is None
    c = analyze_hlo("not hlo at all\n")
    assert c.flops == 0 and c.bytes == 0
