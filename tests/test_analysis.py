"""Static-analysis subsystem: every check class fires on a deliberately
broken fixture, and the real registry passes clean (modulo the checked-in
known-issue baseline).

The fixtures are the point: a checker that never fires is indistinguishable
from one that works, so each contract class gets a minimal function built to
violate exactly it.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import ast_lint, hlo_checks, jaxpr_checks, run
from repro.analysis.contracts import Contract, Violation, contract, registry
from repro.analysis.smoke import SmokeCase


def _case(fn, args, name="fixture", advance=None, donate=()):
    return SmokeCase(name, fn, args, advance=advance, donate_argnums=donate)


def _contract(**kw):
    return Contract(name="fixture", **kw)


# --------------------------------------------------------------------------
# jaxpr checks fire on broken fixtures
# --------------------------------------------------------------------------


def test_host_transfer_check_fires_on_pure_callback():
    def bad(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct((), jnp.float32), x.sum()
        )

    vs = jaxpr_checks.check_host_transfer(
        _case(bad, (jnp.zeros((4,)),)), _contract()
    )
    assert vs and vs[0].check == "host-transfer"
    assert "pure_callback" in vs[0].detail


def test_host_transfer_check_ignores_literal_device_put():
    # jnp.unique(..., fill_value=<python int>) places a literal constant —
    # compile-time folded, must NOT count as a host transfer.
    def ok(x):
        return jnp.unique(x, size=4, fill_value=7)

    assert jaxpr_checks.check_host_transfer(
        _case(ok, (jnp.arange(16),)), _contract()
    ) == []


def test_f64_check_fires_on_double_cast():
    from jax import enable_x64

    def bad(x):
        return x.astype(jnp.float64).sum()

    with enable_x64():
        vs = jaxpr_checks.check_f64(_case(bad, (jnp.zeros((4,)),)), _contract())
    assert vs and vs[0].check == "f64"


def test_f64_check_clean_without_x64():
    def ok(x):
        return (x * 2.0).sum()

    assert jaxpr_checks.check_f64(_case(ok, (jnp.zeros((4,)),)), _contract()) == []


def test_int_counter_check_fires_on_float_counter():
    def bad(state):
        return {"hits": state["hits"].astype(jnp.float32) + 1}

    vs = jaxpr_checks.check_int_counters(
        _case(bad, ({"hits": jnp.zeros((), jnp.int32)},)),
        _contract(int_counters=(r"hits",)),
    )
    assert vs and vs[0].check == "int-counter"
    assert "float32" in vs[0].detail


def test_int_counter_check_passes_on_i32():
    def ok(state):
        return {"hits": state["hits"] + 1}

    assert jaxpr_checks.check_int_counters(
        _case(ok, ({"hits": jnp.zeros((), jnp.int32)},)),
        _contract(int_counters=(r"hits",)),
    ) == []


def test_sort_bound_check_fires_on_full_capacity_argsort():
    def bad(key):
        return jnp.argsort(key, descending=True)

    vs = jaxpr_checks.check_sort_bound(
        _case(bad, (jnp.zeros((4096,)),)), _contract(max_sort_size=64)
    )
    assert vs and vs[0].check == "sort-bound"
    assert "4096" in vs[0].detail


def test_sort_bound_zero_forbids_any_sort():
    vs = jaxpr_checks.check_sort_bound(
        _case(lambda x: jnp.sort(x), (jnp.zeros((8,)),)),
        _contract(max_sort_size=0),
    )
    assert vs and vs[0].check == "sort-bound"


def test_signature_stability_catches_injected_dtype_retrace():
    # step t+1 args drift i32 -> f32: jit would recompile every step.
    def advance(x):
        return (x * 1.0,)

    vs = jaxpr_checks.check_signature_stability(
        _case(lambda x: x, (jnp.zeros((4,), jnp.int32),), advance=advance),
        _contract(),
    )
    assert vs and vs[0].check == "retrace"
    assert "int32" in vs[0].detail and "float32" in vs[0].detail


def test_signature_stability_catches_weak_type_drift():
    # a fresh python-scalar-derived value is WEAKLY typed: same shape+dtype,
    # still a retrace.  This is the classic `state["step"] = 0` bug.
    def advance(x):
        return (jnp.add(1.0, 0.0),)

    vs = jaxpr_checks.check_signature_stability(
        _case(lambda x: x, (jnp.zeros(()),), advance=advance), _contract()
    )
    assert vs and vs[0].check == "retrace"
    assert "weak_type" in vs[0].detail


def test_signature_stability_passes_on_fixed_point_advance():
    assert jaxpr_checks.check_signature_stability(
        _case(lambda x: x + 1, (jnp.zeros((4,), jnp.int32),),
              advance=lambda x: (x + 1,)),
        _contract(),
    ) == []


# --------------------------------------------------------------------------
# HLO checks
# --------------------------------------------------------------------------


def test_donation_check_fires_when_alias_impossible():
    # dtype change makes the donated buffer un-aliasable: double-buffered.
    def bad(state):
        return state["w"].astype(jnp.bfloat16)

    case = _case(bad, ({"w": jnp.zeros((256, 64))},), donate=(0,))
    hlo = hlo_checks.compiled_text(case, donate=True)
    vs = hlo_checks.check_donation(case, _contract(donates=("state",)), hlo)
    assert vs and vs[0].check == "donation"


def test_donation_check_passes_when_elided():
    def ok(state):
        return {"w": state["w"] + 1.0}

    case = _case(ok, ({"w": jnp.zeros((256, 64))},), donate=(0,))
    hlo = hlo_checks.compiled_text(case, donate=True)
    assert hlo_checks.parse_input_output_alias(hlo)
    assert hlo_checks.check_donation(case, _contract(donates=("state",)), hlo) == []


def test_hlo_f64_check_fires():
    from jax import enable_x64

    def bad(x):
        return x.astype(jnp.float64) * 2.0

    with enable_x64():
        case = _case(bad, (jnp.zeros((32,)),))
        hlo = hlo_checks.compiled_text(case)
        vs = hlo_checks.check_f64_hlo(case, _contract(), hlo)
    assert vs and vs[0].check == "f64"


def test_hlo_host_call_check_fires_on_callback():
    def bad(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct((), jnp.float32), x.sum()
        )

    case = _case(bad, (jnp.zeros((4,)),))
    hlo = hlo_checks.compiled_text(case)
    vs = hlo_checks.check_host_calls(case, _contract(), hlo)
    assert vs and vs[0].check == "host-transfer"


# --------------------------------------------------------------------------
# AST lint
# --------------------------------------------------------------------------


def _lint(src):
    return ast_lint.lint_source(src, path="fixture.py", module="fixture")


def test_ast_lint_flags_item_and_float_in_jit_body():
    vs = _lint(
        "import jax\n"
        "@jax.jit\n"
        "def step(state, batch):\n"
        "    n = state.sum().item()\n"
        "    f = float(batch)\n"
        "    return n + f\n"
    )
    assert [v.check for v in vs] == ["ast-host-sync", "ast-host-sync"]
    assert "item" in vs[0].detail and "float" in vs[1].detail


def test_ast_lint_flags_np_asarray_on_traced_value():
    vs = _lint(
        "import jax, numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return np.asarray(x)\n"
    )
    assert vs and vs[0].check == "ast-host-sync"


def test_ast_lint_flags_tracer_branch():
    vs = _lint(
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    while x < 3:\n"
        "        x = x + 1\n"
        "    return x\n"
    )
    assert [v.check for v in vs] == ["ast-tracer-branch", "ast-tracer-branch"]


def test_ast_lint_static_branches_are_clean():
    vs = _lint(
        "import jax\n"
        "@jax.jit\n"
        "def step(cfg, x, n: int, rows=None):\n"
        "    if cfg.writeback:\n"
        "        x = x * 2\n"
        "    if rows is None:\n"
        "        x = x + 1\n"
        "    if isinstance(x, tuple):\n"
        "        x = x[0]\n"
        "    if n > 4:\n"
        "        x = x - 1\n"
        "    return x\n"
    )
    assert vs == []


def test_ast_lint_flags_unregistered_array_dataclass():
    vs = _lint(
        "import dataclasses\n"
        "import jax.numpy as jnp\n"
        "@dataclasses.dataclass\n"
        "class State:\n"
        "    weight: jnp.ndarray\n"
        "    step: int\n"
    )
    assert vs and vs[0].check == "ast-unregistered-dataclass"
    assert "weight" in vs[0].detail


def test_ast_lint_registered_dataclass_is_clean():
    assert _lint(
        "import dataclasses, jax\n"
        "@jax.tree_util.register_dataclass\n"
        "@dataclasses.dataclass\n"
        "class State:\n"
        "    weight: jax.Array\n"
    ) == []
    # Callable fields returning arrays are functions, not array leaves
    assert _lint(
        "import dataclasses\n"
        "from typing import Callable\n"
        "import jax.numpy as jnp\n"
        "@dataclasses.dataclass\n"
        "class Step:\n"
        "    fwd: Callable[..., jnp.ndarray]\n"
    ) == []


def test_ast_lint_flags_inplace_state_mutation():
    vs = _lint(
        "import jax\n"
        "@jax.jit\n"
        "def step(state):\n"
        "    state['k'] = 0\n"
        "    state.hits += 1\n"
        "    local = dict(state)\n"
        "    local['k'] = 1\n"
        "    return state\n"
    )
    assert [v.check for v in vs] == ["ast-state-mutation", "ast-state-mutation"]


def test_ast_lint_extra_jit_covers_registry_methods():
    # undecorated method linted as a jit body because the registry names it
    vs = ast_lint.lint_source(
        "class Coll:\n"
        "    def gather(self, w):\n"
        "        return w.sum().item()\n",
        path="x.py",
        module="repro.fake",
        extra_jit={"repro.fake.Coll.gather"},
    )
    assert vs and vs[0].check == "ast-host-sync"


def test_ast_lint_suppression_comment():
    assert _lint(
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x.sum().item()  # jaxlint: ok\n"
    ) == []


def test_ast_lint_obs_host_sync_outside_allowed_points():
    # in the metric-collection modules, a stray device_get outside the
    # documented once-per-step sync points is flagged...
    src = (
        "import jax\n"
        "def record_metrics(self, metrics):\n"
        "    return float(jax.device_get(metrics['loss']))\n"
    )
    vs = ast_lint.lint_source(src, path="t.py", module="repro.train.trainer")
    assert [v.check for v in vs] == ["ast-obs-host-sync"]
    # ...but the same source outside those modules is host code, not linted
    assert ast_lint.lint_source(src, path="t.py", module="repro.data.synth") == []


def test_ast_lint_obs_host_sync_allows_documented_points():
    assert ast_lint.lint_source(
        "import jax\n"
        "class Trainer:\n"
        "    def _post_step(self, metrics):\n"
        "        loss = float(jax.device_get(metrics['loss']))\n"
        "        n = metrics['overflow'].item()\n"
        "        return loss + n\n",
        path="t.py",
        module="repro.train.trainer",
    ) == []
    assert ast_lint.lint_source(
        "import jax\n"
        "def observe(self, cumulative):\n"
        "    return int(jax.device_get(cumulative))\n",
        path="h.py",
        module="repro.obs.hub",
    ) == []


# --------------------------------------------------------------------------
# registry + runner integration
# --------------------------------------------------------------------------


def test_contract_decorator_registers_without_wrapping():
    from repro.analysis import contracts as contracts_mod

    try:
        @contract(max_sort_size=7, name="tests.fixture_entry")
        def entry(x):
            return x

        reg = registry()
        fn, c = reg["tests.fixture_entry"]
        assert fn is entry  # not wrapped
        assert c.max_sort_size == 7
        assert entry.__contract__ is c
    finally:
        # keep the global registry clean: analyze() treats a registered entry
        # without a smoke case as a 'no-smoke' violation.
        contracts_mod._REGISTRY.pop("tests.fixture_entry", None)


def test_full_gate_passes_clean_modulo_baseline():
    root = Path(__file__).resolve().parents[1]
    report = run.apply_baseline(
        run.analyze(root, passes=("jaxpr", "ast")),
        run.load_baseline(run._DEFAULT_BASELINE),
    )
    assert report["new"] == [], f"new violations on main: {report['new']}"
    # the ROADMAP-item-3 argsorts are FIXED (bounded top-K + fused prepare):
    # the baseline is empty and must stay empty — a new unbounded sort on a
    # registered entry point is a hard failure, not a baseline candidate.
    assert report["baselined"] == []
    assert report["stale_baseline"] == []
    assert len(report["entries"]) >= 24


def test_baseline_marks_stale_entries():
    report = {
        "entries": [], "passes": [], "ast_files": 0,
        "violations": [Violation("sort-bound", "a.b", "x")],
    }
    out = run.apply_baseline(
        report,
        [
            {"check": "sort-bound", "entry": "a.b", "rationale": "known"},
            {"check": "f64", "entry": "gone.entry", "rationale": "fixed"},
        ],
    )
    assert out["ok"] and len(out["baselined"]) == 1
    assert out["stale_baseline"] == [
        {"check": "f64", "entry": "gone.entry", "rationale": "fixed"}
    ]


def test_cli_json_and_exit_codes(tmp_path, capsys):
    # a NEW violation -> exit 1.  The real tree is clean since PR 10 emptied
    # the baseline, so point the AST pass at a synthetic root with a host
    # sync inside a jit body (the jaxpr pass still traces the real registry).
    bad_root = tmp_path / "badrepo"
    (bad_root / "src").mkdir(parents=True)
    (bad_root / "src" / "bad.py").write_text(
        "import jax\n\n@jax.jit\ndef bad(x):\n    return x.item()\n"
    )
    root = str(Path(__file__).resolve().parents[1])
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"known_issues": []}))
    rc = run.main(["--json", "--skip-hlo", "--baseline", str(empty),
                   "--root", str(bad_root)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert "ast-host-sync" in {v["check"] for v in out["new"]}

    # the checked-in baseline -> clean -> exit 0 even under --strict
    rc = run.main(["--json", "--skip-hlo", "--strict", "--root", root])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0 and report["ok"]

    # stale entry + --strict -> exit 2
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({
        "known_issues": json.loads(run._DEFAULT_BASELINE.read_text())["known_issues"]
        + [{"check": "f64", "entry": "no.such.entry", "rationale": "fixed"}]
    }))
    rc = run.main(["--json", "--skip-hlo", "--strict",
                   "--baseline", str(stale), "--root", root])
    capsys.readouterr()
    assert rc == 2
