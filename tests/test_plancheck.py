"""gelly_tpu.analysis.plancheck: compiled-plan contract checker.

Every PC rule is exercised BOTH ways — a seeded-violation fixture that
must flag (line-anchored) and a clean fixture proving the rule's
exemption paths (refusal-scope knob reads, the assignment-chain chase
into the cache key, the rebind idiom, snapshot-through-copy, the
identity carry, axis-derived masks, the full refusal set). Each
historical bug class is re-seeded: the typo'd-``merge_mode`` stale-plan
class against the REAL ``_compiled_plan`` key (PR 4), the
snapshot-aliases-donated-buffer class (PR 10), the masked-lane drift
class (PR 12), and an entry point stripped of its ``stack_ordered``
refusal against the real ``fuse`` (PC4xx). Suppression scoping, the
repo tip (the ISSUE 15 acceptance gate), and the CLI satellites —
shared single-parse AST cache, ``--changed``, ``--format=github``, and
the unparseable-file robustness contract (loud per-file ``SRC001`` from
every tool, never a crash, never a silent skip) — are covered with
exit-code assertions through the unified CLI."""

import json
import os
import subprocess
import textwrap

import pytest

from gelly_tpu.analysis import loader, plancheck
from gelly_tpu.analysis.__main__ import main as analysis_main

pytestmark = pytest.mark.plancheck

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
AGG_PY = os.path.join(REPO, "gelly_tpu", "engine", "aggregation.py")
MQ_PY = os.path.join(REPO, "gelly_tpu", "engine", "multiquery.py")


def _lint_files(tmp_path, files):
    paths = []
    for name, src in files.items():
        p = tmp_path / name
        if isinstance(src, bytes):
            p.write_bytes(src)
        else:
            p.write_text(src)
        paths.append(str(p))
    return plancheck.lint_paths(str(tmp_path), paths)


def _lint_src(tmp_path, src, name="fixture_mod.py"):
    return _lint_files(tmp_path, {name: src})


def _line_of(src, marker):
    for i, line in enumerate(src.splitlines(), 1):
        if marker in line:
            return i
    raise AssertionError(f"marker {marker!r} not in fixture")


# --------------------------------------------------------------------- #
# repo tip (ISSUE 15 acceptance: zero unsuppressed findings, and the
# discovery passes the tip-clean assertion rests on are not vacuous)

def test_plancheck_clean_on_repo_tip():
    findings = plancheck.lint_paths(REPO, [os.path.join(REPO, "gelly_tpu")])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_tip_builder_and_knob_discovery_not_vacuous():
    # The tip-clean assertion above proves nothing if no builder was
    # discovered or the knob universe came up empty: the checker must
    # have found the real memoized builders and classified the real
    # SummaryAggregation fields.
    c = plancheck.PlanChecker(REPO)
    c.lint_paths([os.path.join(REPO, "gelly_tpu")])
    agg_mod = [m for p, m in c._modules.items()
               if p.endswith(os.path.join("engine", "aggregation.py"))][0]
    builders = {b.fn.name for b in c._find_builders(agg_mod)}
    assert {"_compiled_plan", "_compiled_tenant_plan"} <= builders
    assert {"merge_mode", "merge_degree", "transient",
            "jit_transform", "transform_may_alias",
            "stack_ordered"} <= c._scalar_knobs
    assert {"merge_mode"} <= c._str_knobs
    assert {"init", "fold", "combine", "host_compress"} \
        <= c._callable_fields


def test_tip_refusal_matrix_entry_points_all_resolve():
    # Every REFUSAL_MATRIX row names a real (module, function): a rename
    # that forgot the table would flag PC402 on tip — assert the matrix
    # is non-trivial and fully resolved (tip-clean covers the rest).
    assert len(plancheck.REFUSAL_MATRIX) >= 6
    assert sum(len(rows) for rows in plancheck.REFUSAL_MATRIX.values()) \
        >= 15
    c = plancheck.PlanChecker(REPO)
    findings = c.lint_paths([os.path.join(REPO, "gelly_tpu")])
    assert [f for f in findings if f.rule == "PC402"] == []
    linted_bases = {os.path.basename(p) for p in c._modules}
    for base, _fn in plancheck.REFUSAL_MATRIX:
        assert base in linted_bases, base


# --------------------------------------------------------------------- #
# shared fixture pieces

AGG_SRC = textwrap.dedent('''\
    import dataclasses
    from typing import Any, Callable


    @dataclasses.dataclass
    class SummaryAggregation:
        name: str
        init: Callable[[], Any]
        fold: Callable[[Any, Any], Any]
        fold_backend: str = "jit"
        merge_mode: str = "tree"
        merge_degree: int = 8
        transient: bool = False
        jit_transform: bool = True


''')

# --------------------------------------------------------------------- #
# PC101: the PR 4 merge_mode bug class — a knob the builder reads but
# the cache key does not carry. The fold_backend read inside the
# if-raise refusal is the documented exemption (reads that only feed a
# refusal need no keying), and doubles as its PC102 validation.

PC101_SRC = AGG_SRC + textwrap.dedent('''\
    def _compiled_plan(agg, mesh):
        key = (tuple(mesh.axis_names), agg.fold_backend, agg.merge_degree)
        per = agg.__dict__.setdefault("_plan_cache", {})
        if key in per:
            return per[key]
        if agg.fold_backend not in ("jit", "pallas"):
            raise ValueError("unknown fold_backend")
        mode = agg.merge_mode                            # M-PC101
        def fold_chunk(state, chunk):
            return agg.fold(state, chunk)
        plan = (fold_chunk, mode)
        per[key] = plan
        return plan
''')


def test_pc101_unkeyed_knob_flags_line_anchored(tmp_path):
    findings = _lint_src(tmp_path, PC101_SRC)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC101", _line_of(PC101_SRC, "M-PC101"))], \
        "\n".join(f.render() for f in findings)
    assert "merge_mode" in findings[0].message
    assert findings[0].hint


def test_pc101_keyed_knob_is_clean(tmp_path):
    # Keying merge_mode fixes PC101; as a str key knob it then needs
    # its own allowed-set validation (PC102), provided by a sibling.
    src = PC101_SRC.replace(
        "key = (tuple(mesh.axis_names), agg.fold_backend, agg.merge_degree)",
        "key = (tuple(mesh.axis_names), agg.fold_backend,\n"
        "           agg.merge_mode, agg.merge_degree)")
    findings = _lint_files(tmp_path, {"fixture_mod.py": src,
                                      "validators.py": VALIDATOR_SRC})
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc101_read_inside_a_refusal_is_exempt(tmp_path):
    # Dropping the unkeyed read leaves only the refusal-scoped
    # fold_backend read and the keyed ones: exempt, clean.
    src = PC101_SRC.replace(
        "    mode = agg.merge_mode                            # M-PC101\n",
        "")
    src = src.replace("plan = (fold_chunk, mode)", "plan = (fold_chunk,)")
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc101_real_compiled_plan_key_drop_flips(tmp_path):
    # The PR 4 bug class re-seeded against the REAL builder: drop
    # agg.merge_mode from _compiled_plan's key tuple and the checker
    # must flag the builder's surviving merge_mode reads.
    with open(AGG_PY) as f:
        src = f.read()
    needle = "agg.merge_mode, agg.merge_degree,"
    assert src.count(needle) == 1, \
        "the _compiled_plan key line moved — re-anchor"
    mutated = src.replace(needle, "agg.merge_degree,")
    got = _lint_src(tmp_path, mutated, name="aggregation.py")
    pc101 = [f for f in got if f.rule == "PC101"]
    assert pc101 and all("merge_mode" in f.message for f in pc101), \
        "\n".join(f.render() for f in got)
    # control: the unmodified file carries no PC101 (single-file lint
    # may raise package-scoped PC102 noise; PC101 is the re-seed).
    clean = _lint_src(tmp_path, src, name="aggregation.py")
    assert [f for f in clean if f.rule == "PC101"] == [], \
        "\n".join(f.render() for f in clean)


# --------------------------------------------------------------------- #
# PC102: a str-typed key knob with no allowed-set membership check in
# the whole package — the typo that silently selects the wrong plan.

PC102_SRC = AGG_SRC + textwrap.dedent('''\
    def _compiled_plan(agg, mesh):
        key = (tuple(mesh.axis_names), agg.merge_mode)   # M-PC102
        per = agg.__dict__.setdefault("_plan_cache", {})
        if key in per:
            return per[key]
        def fold_chunk(state, chunk):
            return agg.fold(state, chunk)
        plan = (fold_chunk,)
        per[key] = plan
        return plan
''')

VALIDATOR_SRC = textwrap.dedent('''\
    def resolve_merge_mode(agg):
        if agg.merge_mode not in ("tree", "delta"):
            raise ValueError("unknown merge_mode: " + agg.merge_mode)
        return agg.merge_mode
''')


def test_pc102_unvalidated_str_knob_flags_at_the_key(tmp_path):
    findings = _lint_src(tmp_path, PC102_SRC)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC102", _line_of(PC102_SRC, "M-PC102"))], \
        "\n".join(f.render() for f in findings)
    assert "merge_mode" in findings[0].message


def test_pc102_sibling_module_validation_is_clean(tmp_path):
    # "Validated SOMEWHERE in the package": the resolve_merge_mode
    # pattern in a sibling module satisfies the rule.
    findings = _lint_files(tmp_path, {"fixture_mod.py": PC102_SRC,
                                      "validators.py": VALIDATOR_SRC})
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc102_inactive_on_a_partial_package_subset(tmp_path):
    # A sibling module on disk but NOT in the lint set means "validated
    # nowhere" may be under-collection: PC102 must stay silent (the
    # OB002 precedent).
    (tmp_path / "validators.py").write_text(VALIDATOR_SRC)
    p = tmp_path / "fixture_mod.py"
    p.write_text(PC102_SRC)
    findings = plancheck.lint_paths(str(tmp_path), [str(p)])
    assert findings == [], "\n".join(f.render() for f in findings)


# --------------------------------------------------------------------- #
# PC103: a builder parameter (mesh, lane width, ...) read by the plan
# but unreachable from the key — a plan compiled for another width.

PC103_SRC = AGG_SRC + textwrap.dedent('''\
    def _compiled_plan(agg, mesh, width):
        key = (tuple(mesh.axis_names), agg.merge_degree)  # M-PC103
        per = agg.__dict__.setdefault("_plan_cache", {})
        if key in per:
            return per[key]
        rows = width * agg.merge_degree
        def fold_chunk(state, chunk):
            return agg.fold(state, chunk)
        plan = (fold_chunk, rows)
        per[key] = plan
        return plan
''')


def test_pc103_unkeyed_parameter_flags_at_the_key(tmp_path):
    findings = _lint_src(tmp_path, PC103_SRC)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC103", _line_of(PC103_SRC, "M-PC103"))], \
        "\n".join(f.render() for f in findings)
    assert "'width'" in findings[0].message


def test_pc103_refusal_only_parameter_is_exempt(tmp_path):
    # A parameter whose only read feeds a refusal guard needs no
    # keying (the PC101 exemption, applied symmetrically).
    src = PC103_SRC.replace(
        "    rows = width * agg.merge_degree\n",
        "    if width is None:\n"
        "        raise ValueError(\"width is required\")\n")
    src = src.replace("plan = (fold_chunk, rows)", "plan = (fold_chunk,)")
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc103_assignment_chain_into_the_key_is_clean(tmp_path):
    # `lanes = (width, ...)` then `key = (lanes, ...)`: the coverage
    # chase follows simple assignment chains into the key tuple.
    src = PC103_SRC.replace(
        "key = (tuple(mesh.axis_names), agg.merge_degree)  # M-PC103",
        "lanes = (width, tuple(mesh.axis_names))\n"
        "    key = (lanes, agg.merge_degree)")
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


# --------------------------------------------------------------------- #
# PC201: the PR 10 bug class — a snapshot path inside a donating
# builder returning the live state instead of an eager copy.

PC201_SRC = textwrap.dedent('''\
    import jax
    import jax.numpy as jnp


    def _fold(state, chunk):
        return state


    def _compiled_plan(agg, mesh):
        key = (agg.fold_backend, agg.merge_degree)
        per = agg.__dict__.setdefault("_plan_cache", {})
        if key in per:
            return per[key]
        fold_chunk = jax.jit(_fold, donate_argnums=(0,))
        def snapshot(state):                             # M-PC201
            return state
        plan = (fold_chunk, snapshot)
        per[key] = plan
        return plan
''')


def test_pc201_snapshot_without_copy_flags(tmp_path):
    findings = _lint_src(tmp_path, PC201_SRC)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC201", _line_of(PC201_SRC, "M-PC201"))], \
        "\n".join(f.render() for f in findings)
    assert "'snapshot'" in findings[0].message


def test_pc201_eager_copy_is_clean(tmp_path):
    src = PC201_SRC.replace(
        "        return state\n    plan",
        "        return jax.tree.map(jnp.copy, state)\n    plan")
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc201_inactive_without_donation(tmp_path):
    # The same bare-return snapshot in a NON-donating builder is the
    # documented cheap path (no buffer is ever deleted) — clean.
    src = PC201_SRC.replace(", donate_argnums=(0,)", "")
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


# --------------------------------------------------------------------- #
# PC202: a donated fold called outside the rebind idiom keeps a
# poisoned reference (TPU-only 'Array has been deleted', invisible on
# the CPU test tier).

PC202_SRC = textwrap.dedent('''\
    def serve(agg, mesh, chunks, sink):
        plan = _compiled_plan(agg, mesh)
        state, fold_chunk = plan
        for chunk in chunks:
            sink.append(fold_chunk(state, chunk))        # M-PC202
        return state
''')


def test_pc202_unrebound_fold_call_flags(tmp_path):
    findings = _lint_src(tmp_path, PC202_SRC)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC202", _line_of(PC202_SRC, "M-PC202"))], \
        "\n".join(f.render() for f in findings)


def test_pc202_rebind_idiom_is_clean(tmp_path):
    src = textwrap.dedent('''\
        def serve(agg, mesh, chunks, sink):
            plan = _compiled_plan(agg, mesh)
            state, fold_chunk = plan
            for chunk in chunks:
                state = fold_chunk(state, chunk)
                sink.append(plan.snapshot(state))
            return state
    ''')
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc202_attribute_receiver_both_polarities(tmp_path):
    # `<x>.plan.fold(...)` is donated wherever it appears: the bare
    # call flags, the rebound call two lines down stays clean.
    src = textwrap.dedent('''\
        def step(batch, state, chunk, out):
            out.result = batch.plan.fold(state, chunk)   # M-PC202-ATTR
            state = batch.plan.fold(state, chunk)
            return state
    ''')
    findings = _lint_src(tmp_path, src)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC202", _line_of(src, "M-PC202-ATTR"))], \
        "\n".join(f.render() for f in findings)


def test_pc202_rebinding_the_name_clears_donation(tmp_path):
    # `fold_chunk = identity` shadows the donated binding: calls after
    # the rebind are ordinary calls, not donation sites.
    src = textwrap.dedent('''\
        def serve(agg, mesh, chunk, sink):
            plan = _compiled_plan(agg, mesh)
            state, fold_chunk = plan
            fold_chunk = make_plain_fold(agg)
            sink.append(fold_chunk(state, chunk))
            return state
    ''')
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


# --------------------------------------------------------------------- #
# PC203: publishing the live donated state to a snapshot/latest slot —
# queries then read buffers the next dispatch invalidates.

PC203_SRC = textwrap.dedent('''\
    def serve(agg, mesh, chunk, store):
        plan = _compiled_plan(agg, mesh)
        state, fold_chunk = plan
        store.latest_summary = state                     # M-PC203
        state = fold_chunk(state, chunk)
        return state
''')


def test_pc203_live_state_publication_flags(tmp_path):
    findings = _lint_src(tmp_path, PC203_SRC)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC203", _line_of(PC203_SRC, "M-PC203"))], \
        "\n".join(f.render() for f in findings)
    assert "latest_summary" in findings[0].message


def test_pc203_snapshot_call_is_clean(tmp_path):
    src = PC203_SRC.replace(
        "store.latest_summary = state                     # M-PC203",
        "store.latest_summary = plan.snapshot(state)")
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc203_alias_hop_does_not_launder(tmp_path):
    # `snap = state; store.latest = snap` — the chase follows the
    # simple-assignment hop back to the live expression.
    src = textwrap.dedent('''\
        def serve(agg, mesh, chunk, store):
            plan = _compiled_plan(agg, mesh)
            state, fold_chunk = plan
            snap = state
            store.latest_summary = snap                  # M-HOP
            state = fold_chunk(state, chunk)
            return state
    ''')
    findings = _lint_src(tmp_path, src)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC203", _line_of(src, "M-HOP"))], \
        "\n".join(f.render() for f in findings)


# --------------------------------------------------------------------- #
# PC301/PC302: the PR 12 bug class — masked no-op lanes must carry the
# original leaf bit-unchanged, under a mask derived from the lane axis.

PC301_SRC = textwrap.dedent('''\
    import jax
    import jax.numpy as jnp


    def masked_fold(state, new, mask):
        return jax.tree.map(
            lambda s, n: jnp.where(mask, n, jnp.zeros_like(s)),  # M-PC301
            state, new)
''')


def test_pc301_non_identity_false_branch_flags(tmp_path):
    findings = _lint_src(tmp_path, PC301_SRC)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC301", _line_of(PC301_SRC, "M-PC301"))], \
        "\n".join(f.render() for f in findings)
    assert "jnp.zeros_like(s)" in findings[0].message


def test_pc301_identity_carry_is_clean(tmp_path):
    src = PC301_SRC.replace(
        "jnp.where(mask, n, jnp.zeros_like(s)),  # M-PC301",
        "jnp.where(mask, n, s),")
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc301_arithmetic_on_the_carry_flags(tmp_path):
    # `s + 0` is bit-identical for ints but NOT for floats (-0.0, NaN
    # payloads): only the bare leaf is the identity carry.
    src = PC301_SRC.replace("jnp.zeros_like(s)", "s + 0")
    findings = _lint_src(tmp_path, src)
    assert [f.rule for f in findings] == ["PC301"], \
        "\n".join(f.render() for f in findings)


PC302_SRC = textwrap.dedent('''\
    import jax
    import jax.numpy as jnp

    _DEFAULT_LANES = 8


    def masked_fold(state, new):
        mask = jnp.arange(_DEFAULT_LANES) < 4
        return jax.tree.map(
            lambda s, n: jnp.where(mask, n, s),          # M-PC302
            state, new)
''')


def test_pc302_constant_derived_mask_flags(tmp_path):
    findings = _lint_src(tmp_path, PC302_SRC)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC302", _line_of(PC302_SRC, "M-PC302"))], \
        "\n".join(f.render() for f in findings)


def test_pc302_parameter_derived_mask_is_clean(tmp_path):
    src = PC302_SRC.replace(
        "def masked_fold(state, new):",
        "def masked_fold(state, new, active):",
    ).replace("mask = jnp.arange(_DEFAULT_LANES) < 4",
              "mask = active > 0")
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc302_axis_index_mask_is_clean(tmp_path):
    src = PC302_SRC.replace(
        "mask = jnp.arange(_DEFAULT_LANES) < 4",
        'mask = jax.lax.axis_index("lanes") < 4')
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


# --------------------------------------------------------------------- #
# PC4xx: the eligibility refusal matrix. The fixture mirrors fuse()'s
# refusal set; the checker keys on the module BASENAME, so the fixture
# file is named multiquery.py.

FUSE_SRC = textwrap.dedent('''\
    class MultiQueryPlan:
        pass


    def fuse(queries):                                   # M-PC401
        for q in queries:
            if isinstance(q, MultiQueryPlan):
                raise TypeError("nested fusion is unsupported")
            if q.agg.transient:
                raise ValueError("transient sub-plans are unsupported")
            if q.agg.windowed_panes:
                raise ValueError("windowed_panes rings are unsupported")
            if not q.agg.jit_transform:
                raise ValueError("host-side transforms are unsupported")
            codec = q.codec
            if codec is not None and codec.stack_ordered:
                raise ValueError("stack_ordered codecs are unsupported")
            if q.agg.requires_codec and codec is None:
                raise ValueError("requires_codec without a codec")
        return queries
''')


def test_pc401_full_refusal_set_is_clean(tmp_path):
    findings = _lint_src(tmp_path, FUSE_SRC, name="multiquery.py")
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc401_stripped_refusal_flags_the_entry_point(tmp_path):
    src = FUSE_SRC.replace(
        '        if codec is not None and codec.stack_ordered:\n'
        '            raise ValueError("stack_ordered codecs are '
        'unsupported")\n', "")
    findings = _lint_src(tmp_path, src, name="multiquery.py")
    assert [(f.rule, f.line) for f in findings] \
        == [("PC401", _line_of(src, "M-PC401"))], \
        "\n".join(f.render() for f in findings)
    assert "stack_ordered" in findings[0].message


def test_pc401_basename_scoping(tmp_path):
    # The same stripped body under a NON-matrix basename is not an
    # entry point: the matrix binds (module, function) pairs only.
    src = FUSE_SRC.replace(
        '        if codec is not None and codec.stack_ordered:\n'
        '            raise ValueError("stack_ordered codecs are '
        'unsupported")\n', "")
    findings = _lint_src(tmp_path, src, name="helpers.py")
    assert findings == [], "\n".join(f.render() for f in findings)


def test_pc401_real_fuse_stripped_of_stack_ordered_flips(tmp_path):
    # The acceptance re-seed against the REAL entry point: renaming the
    # stack_ordered eligibility tokens out of fuse()'s guards (the
    # shape a refactor that silently drops the refusal produces) must
    # flag PC401 for exactly that matrix row.
    with open(MQ_PY) as f:
        src = f.read()
    assert "stack_ordered" in src, "fuse() eligibility moved — re-anchor"
    mutated = src.replace("stack_ordered", "stack_reordered")
    got = _lint_src(tmp_path, mutated, name="multiquery.py")
    pc401 = [f for f in got if f.rule == "PC401"]
    assert len(pc401) == 1 and "stack_ordered" in pc401[0].message, \
        "\n".join(f.render() for f in got)
    # control: the unmodified module satisfies every matrix row.
    clean = _lint_src(tmp_path, src, name="multiquery.py")
    assert [f for f in clean if f.rule.startswith("PC4")] == [], \
        "\n".join(f.render() for f in clean)


def test_pc402_renamed_entry_point_flags(tmp_path):
    src = FUSE_SRC.replace("def fuse(", "def fuse_everything(")
    findings = _lint_src(tmp_path, src, name="multiquery.py")
    assert [(f.rule, f.line) for f in findings] == [("PC402", 1)], \
        "\n".join(f.render() for f in findings)
    assert "'fuse'" in findings[0].message


def test_matrix_dirs_cover_every_matrix_module():
    # The missing-module PC402 scope map must name every matrix
    # module, or a future entry silently opts out of rename detection.
    assert set(plancheck._MATRIX_DIRS) \
        == {base for base, _fn in plancheck.REFUSAL_MATRIX}


def test_pc402_renamed_module_file_flags(tmp_path):
    # `git mv engine/multiquery.py engine/mq.py` must not silently
    # drop fuse()'s whole refusal check: a matrix module missing from
    # its linted home package flags PC402. Fixture dirs (no `engine`
    # package) stay out of scope — every other test here proves that.
    eng = tmp_path / "engine"
    eng.mkdir()
    (eng / "__init__.py").write_text("")
    (eng / "mq.py").write_text(FUSE_SRC)  # renamed: no multiquery.py
    findings = plancheck.lint_paths(
        str(tmp_path), [str(eng / "__init__.py"), str(eng / "mq.py")])
    missing = {f.message.split("'")[1] for f in findings
               if f.rule == "PC402"}
    assert "multiquery.py" in missing, \
        "\n".join(f.render() for f in findings)
    assert all(f.rule == "PC402" for f in findings)
    # restoring the canonical name clears the missing-module half
    (eng / "mq.py").rename(eng / "multiquery.py")
    findings = plancheck.lint_paths(
        str(tmp_path),
        [str(eng / "__init__.py"), str(eng / "multiquery.py")])
    assert "multiquery.py" not in {
        f.message.split("'")[1] for f in findings if f.rule == "PC402"}


# --------------------------------------------------------------------- #
# suppression scoping

def test_suppression_silences_one_rule_one_line(tmp_path):
    src = PC101_SRC.replace(
        "mode = agg.merge_mode                            # M-PC101",
        "mode = agg.merge_mode  # graphlint: disable=PC101")
    findings = _lint_src(tmp_path, src)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_suppression_wrong_rule_and_all(tmp_path):
    src = PC101_SRC.replace(
        "mode = agg.merge_mode                            # M-PC101",
        "mode = agg.merge_mode  # graphlint: disable=PC202")
    assert [f.rule for f in _lint_src(tmp_path, src)] == ["PC101"]
    src2 = PC101_SRC.replace(
        "mode = agg.merge_mode                            # M-PC101",
        "mode = agg.merge_mode  # graphlint: disable=all")
    assert _lint_src(tmp_path, src2) == []


def test_suppression_does_not_leak_to_other_lines(tmp_path):
    # Suppressing the PC202 call must not blot out the PC203 store two
    # lines up (per-line, per-rule scoping).
    src = textwrap.dedent('''\
        def serve(agg, mesh, chunk, store, sink):
            plan = _compiled_plan(agg, mesh)
            state, fold_chunk = plan
            store.latest_summary = state                 # M-KEEP
            sink.append(
                fold_chunk(state, chunk))  # graphlint: disable=PC202
            return state
    ''')
    findings = _lint_src(tmp_path, src)
    assert [(f.rule, f.line) for f in findings] \
        == [("PC203", _line_of(src, "M-KEEP"))], \
        "\n".join(f.render() for f in findings)


# --------------------------------------------------------------------- #
# every seeded violation flips the CLI exit code (ISSUE 15 acceptance)

_RULE_SEEDS = {
    "PC101": {"fixture_mod.py": PC101_SRC},
    "PC102": {"fixture_mod.py": PC102_SRC},
    "PC103": {"fixture_mod.py": PC103_SRC},
    "PC201": {"fixture_mod.py": PC201_SRC},
    "PC202": {"fixture_mod.py": PC202_SRC},
    "PC203": {"fixture_mod.py": PC203_SRC},
    "PC301": {"fixture_mod.py": PC301_SRC},
    "PC302": {"fixture_mod.py": PC302_SRC},
    "PC401": {"multiquery.py": FUSE_SRC.replace(
        '        if codec is not None and codec.stack_ordered:\n'
        '            raise ValueError("stack_ordered codecs are '
        'unsupported")\n', "")},
    "PC402": {"multiquery.py": FUSE_SRC.replace(
        "def fuse(", "def fuse_everything(")},
}


@pytest.mark.parametrize("rule", sorted(_RULE_SEEDS))
def test_seeded_violation_turns_exit_nonzero(tmp_path, rule, capsys):
    for name, src in _RULE_SEEDS[rule].items():
        (tmp_path / name).write_text(src)
    rc = analysis_main(["plancheck", str(tmp_path), "--root", REPO])
    out = capsys.readouterr().out
    assert rc == 1
    assert rule in out


def test_cli_plancheck_subcommand_exit_zero_on_tip(capsys):
    rc = analysis_main(["plancheck", os.path.join(REPO, "gelly_tpu"),
                        "--root", REPO])
    out = capsys.readouterr().out
    assert rc == 0
    assert "plancheck: 0 finding(s)" in out
    assert "analysis clean (plancheck)" in out


def test_cli_list_rules_includes_pc_rules_and_src(capsys):
    assert analysis_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("PC101", "PC102", "PC103", "PC201", "PC202", "PC203",
                "PC301", "PC302", "PC401", "PC402", "SRC001"):
        assert rid in out, rid


@pytest.mark.slow  # tier-1 budget: plancheck lane; subcommand smoke stays
def test_cli_skip_plancheck(capsys):
    rc = analysis_main(["--all", "--root", REPO, "--skip-plancheck",
                        "--format=json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert set(payload["tools"]) == {"abi", "jitlint", "racecheck",
                                     "contracts", "liveness"}


# --------------------------------------------------------------------- #
# analyzer robustness (satellite): a syntax error, a zero-byte file,
# and a non-UTF8 file must each produce one loud per-file SRC001 from
# EVERY covering tool — not a crash, not a silent skip.

_BROKEN_TREE = {
    "bad_syntax.py": "def broken(:\n    pass\n",
    "empty_mod.py": "",
    "not_utf8.py": b"x = '\xff\xfe'\n",
}


def test_unparseable_files_are_loud_from_every_tool(tmp_path, capsys):
    for name, src in _BROKEN_TREE.items():
        p = tmp_path / name
        p.write_bytes(src if isinstance(src, bytes) else src.encode())
    rc = analysis_main(["--all", str(tmp_path), "--root", REPO,
                        "--format=json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    for tool in ("jitlint", "racecheck", "contracts", "plancheck",
                 "liveness"):
        fs = payload["tools"][tool]["findings"]
        assert all(f["rule"] == "SRC001" for f in fs), (tool, fs)
        names = {os.path.basename(f["path"]) for f in fs}
        assert names == set(_BROKEN_TREE), (tool, names)
    # each failure kind names its cause (one tool's stream suffices)
    msgs = " ".join(f["message"]
                    for f in payload["tools"]["plancheck"]["findings"])
    assert "syntax error" in msgs
    assert "zero-byte" in msgs
    assert "not valid UTF-8" in msgs


@pytest.mark.parametrize("tool", ["jitlint", "racecheck", "contracts",
                                  "plancheck", "liveness"])
def test_single_tool_cli_exit_nonzero_on_broken_file(tmp_path, tool,
                                                     capsys):
    (tmp_path / "bad_syntax.py").write_text("def broken(:\n")
    rc = analysis_main([tool, str(tmp_path), "--root", REPO])
    out = capsys.readouterr().out
    assert rc == 1
    assert "SRC001" in out


def test_syntax_error_finding_is_line_anchored(tmp_path):
    findings = _lint_src(tmp_path, "ok = 1\ndef broken(:\n",
                         name="bad_syntax.py")
    assert [(f.rule, f.line) for f in findings] == [("SRC001", 2)]


def test_empty_init_py_is_exempt(tmp_path):
    # An empty package marker is idiomatic, not a truncation.
    findings = _lint_files(tmp_path, {"__init__.py": "",
                                      "mod.py": "x = 1\n"})
    assert findings == [], "\n".join(f.render() for f in findings)


def test_whitespace_only_module_is_not_a_truncation(tmp_path):
    # Only a literally zero-byte file is the truncation signal: a
    # whitespace/newline-only module is valid (empty) Python.
    findings = _lint_files(tmp_path, {"placeholder.py": "\n\n",
                                      "mod.py": "x = 1\n"})
    assert findings == [], "\n".join(f.render() for f in findings)


def test_nul_byte_file_is_a_finding_not_a_crash(tmp_path):
    # ast.parse rejects NUL bytes with a bare ValueError (a truncated
    # binary write): same contract — loud SRC001, never a traceback.
    findings = _lint_files(tmp_path, {"nulled.py": b"x = 1\x00\n"})
    assert [f.rule for f in findings] == ["SRC001"], \
        "\n".join(f.render() for f in findings)


def test_src001_is_deduplicated_per_tool(tmp_path, capsys):
    # One broken file, one SRC001 per tool — not one per rule pass.
    (tmp_path / "bad_syntax.py").write_text("def broken(:\n")
    rc = analysis_main(["--all", str(tmp_path), "--root", REPO,
                        "--format=json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    for tool in ("jitlint", "racecheck", "contracts", "plancheck",
                 "liveness"):
        assert payload["tools"][tool]["count"] == 1, tool


# --------------------------------------------------------------------- #
# shared single-parse AST cache (satellite)

def test_source_cache_parses_each_file_once(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("def f():\n    return 1\n")
    cache = loader.SourceCache()
    a = cache.get(str(p))
    b = cache.get(str(p))
    assert a is b and a.tree is b.tree


def test_all_tools_share_one_parse_per_file(tmp_path, monkeypatch):
    # The satellite's contract made observable: under --all, no file is
    # ast.parse-d more than once per CLI invocation.
    (tmp_path / "mod_a.py").write_text("def f():\n    return 1\n")
    (tmp_path / "mod_b.py").write_text("def g():\n    return 2\n")
    counts = {}
    real_parse = loader.ast.parse

    def counting(src, filename="<unknown>", *args, **kwargs):
        counts[filename] = counts.get(filename, 0) + 1
        return real_parse(src, filename, *args, **kwargs)

    monkeypatch.setattr(loader.ast, "parse", counting)
    rc = analysis_main(["--all", str(tmp_path), "--root", REPO,
                        "--skip-abi"])
    assert rc == 0
    fixture_counts = {os.path.basename(f): n for f, n in counts.items()
                      if f.startswith(str(tmp_path))}
    assert fixture_counts == {"mod_a.py": 1, "mod_b.py": 1}
    assert counts and max(counts.values()) == 1, \
        {f: n for f, n in counts.items() if n > 1}


# --------------------------------------------------------------------- #
# --changed fast path (satellite)

def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=ci@test", "-c", "user.name=ci", *args],
        cwd=str(cwd), check=True, capture_output=True)


def test_changed_reports_only_changed_file_findings(tmp_path, capsys):
    _git(tmp_path, "init", "-q")
    (tmp_path / "old_mod.py").write_text(PC202_SRC)
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (tmp_path / "new_mod.py").write_text(PC203_SRC)  # untracked
    rc = analysis_main(["plancheck", str(tmp_path), "--root",
                        str(tmp_path), "--changed", "--format=json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    names = {os.path.basename(f["path"])
             for f in payload["tools"]["plancheck"]["findings"]}
    assert names == {"new_mod.py"}


def test_changed_clean_when_everything_is_committed(tmp_path, capsys):
    # Violations exist in the tree, but nothing differs from HEAD: the
    # fast path reports nothing and exits 0 (the full lane still runs
    # the whole-package walk in CI).
    _git(tmp_path, "init", "-q")
    (tmp_path / "old_mod.py").write_text(PC202_SRC)
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    rc = analysis_main(["plancheck", str(tmp_path), "--root",
                        str(tmp_path), "--changed"])
    capsys.readouterr()
    assert rc == 0


def test_changed_against_an_explicit_ref(tmp_path, capsys):
    _git(tmp_path, "init", "-q")
    (tmp_path / "old_mod.py").write_text("x = 1\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (tmp_path / "new_mod.py").write_text(PC202_SRC)
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "grow")
    assert analysis_main(["plancheck", str(tmp_path), "--root",
                          str(tmp_path), "--changed"]) == 0
    capsys.readouterr()
    rc = analysis_main(["plancheck", str(tmp_path), "--root",
                        str(tmp_path), "--changed=HEAD~1",
                        "--format=json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    names = {os.path.basename(f["path"])
             for f in payload["tools"]["plancheck"]["findings"]}
    assert names == {"new_mod.py"}


def test_changed_files_root_below_git_toplevel(tmp_path):
    # `git diff --name-only` prints toplevel-relative paths; with
    # --root pointing at a subdirectory of the repo, tracked changes
    # must still resolve to real absolute paths (untracked files are
    # cwd-relative and take the other join base).
    from gelly_tpu.analysis.__main__ import _changed_files

    _git(tmp_path, "init", "-q")
    sub = tmp_path / "vendor"
    sub.mkdir()
    (sub / "a.py").write_text("x = 1\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (sub / "a.py").write_text("x = 2\n")          # tracked, modified
    (sub / "b.py").write_text("y = 1\n")          # untracked
    changed = _changed_files(str(sub), "HEAD")
    assert str(sub / "a.py") in changed
    assert str(sub / "b.py") in changed


def test_changed_space_separated_ref_form(tmp_path, capsys):
    # `--changed HEAD~1` (space form) must consume the ref, not demote
    # it to a lint path and silently diff against HEAD.
    _git(tmp_path, "init", "-q")
    (tmp_path / "old_mod.py").write_text("x = 1\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (tmp_path / "new_mod.py").write_text(PC202_SRC)
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "grow")
    rc = analysis_main(["plancheck", str(tmp_path), "--root",
                        str(tmp_path), "--changed", "HEAD~1",
                        "--format=json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    names = {os.path.basename(f["path"])
             for f in payload["tools"]["plancheck"]["findings"]}
    assert names == {"new_mod.py"}


def test_changed_does_not_mask_unparseable_unchanged_files(tmp_path,
                                                           capsys):
    # A broken file the diff scope would exclude still flips the exit
    # code: the whole-package rules ran blind over it, so the fast
    # path must not report "clean" (SRC001 is scope-exempt).
    _git(tmp_path, "init", "-q")
    (tmp_path / "broken.py").write_text("def broken(:\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (tmp_path / "new_mod.py").write_text("x = 1\n")
    rc = analysis_main(["plancheck", str(tmp_path), "--root",
                        str(tmp_path), "--changed", "--format=json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert [f["rule"]
            for f in payload["tools"]["plancheck"]["findings"]] \
        == ["SRC001"]


def test_changed_bad_ref_is_a_loud_error(tmp_path):
    _git(tmp_path, "init", "-q")
    with pytest.raises(SystemExit):
        analysis_main(["plancheck", str(tmp_path), "--root",
                       str(tmp_path), "--changed=no-such-ref"])


# --------------------------------------------------------------------- #
# --format=github workflow annotations (satellite)

def test_github_format_emits_error_annotations(tmp_path, capsys):
    (tmp_path / "fixture_mod.py").write_text(PC202_SRC)
    rc = analysis_main(["plancheck", str(tmp_path), "--root",
                        str(tmp_path), "--format=github"])
    out = capsys.readouterr().out
    assert rc == 1
    ann = [ln for ln in out.splitlines() if ln.startswith("::error ")]
    assert len(ann) == 1
    line = _line_of(PC202_SRC, "M-PC202")
    assert ann[0].startswith(
        f"::error file=fixture_mod.py,line={line},title=PC202::")
    assert "hint:" in ann[0]


def test_github_format_escapes_workflow_command_data(tmp_path, capsys):
    # %, CR and LF in the message/hint must be %-escaped or GitHub
    # truncates the annotation at the first newline.
    (tmp_path / "fixture_mod.py").write_text(PC202_SRC)
    rc = analysis_main(["plancheck", str(tmp_path), "--root",
                        str(tmp_path), "--format=github"])
    out = capsys.readouterr().out
    assert rc == 1
    for ln in out.splitlines():
        if ln.startswith("::error "):
            assert "\r" not in ln and len(ln.splitlines()) == 1


def test_github_annotation_escapes_property_delimiters():
    # ',' and ':' in property values are workflow-command delimiters
    # and must be %-escaped or GitHub mis-parses the annotation.
    from gelly_tpu.analysis import Finding
    from gelly_tpu.analysis.__main__ import _github_annotation

    f = Finding("/r/a,b/mod.py", 3, "PC202", "msg: 100% broken",
                hint="h")
    ann = _github_annotation(f, "/r")
    assert ann.startswith("::error file=a%2Cb/mod.py,line=3,"
                          "title=PC202::")
    assert ann.endswith("msg: 100%25 broken | hint: h")


def test_github_format_clean_tip_emits_no_annotations(capsys):
    rc = analysis_main(["plancheck", os.path.join(REPO, "gelly_tpu"),
                        "--root", REPO, "--format=github"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "::error" not in out
    assert "plancheck: 0 finding(s)" in out
