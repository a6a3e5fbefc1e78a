"""The lint gate (cpd_tpu.analysis) — fixture-proven rules + a clean
live tree, now with the v2 whole-program layer.

Layers under test:

1. every rule — module-scoped AND project-scoped — has a deliberately-
   bad fixture that MUST fire (true positive) and a clean twin that MUST
   stay silent under the whole catalog (true negative);
2. the suppression grammar (line / file / skip-file) is honored, and the
   live tree's suppression count is pinned (suppressions are reviewed
   claims, not escapes — a new one must update the pin with its
   justification);
3. the whole-program layer: cross-FILE propagation (the per-file v1
   could never see), the fingerprint cache (warm run == zero re-parses,
   edits invalidate), config precedence ([tool.cpd-lint] >
   built-in defaults, --config over both);
4. the real tree — cpd_tpu, tests, tools, examples — lints clean under
   the FULL v2 rule set, so any regression fails pytest without a
   separate CI system, and the CLI's exit-code contract (0 clean /
   1 findings / 2 internal error) plus the JSON v1 and SARIF 2.1.0
   shapes stay pinned for tooling.

The analysis package is stdlib-only, so this file runs without jax.
"""

import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

import pytest

from cpd_tpu.analysis import (all_rules, host_rules, lint_file,
                              lint_source, lint_tree, module_rules,
                              program_rules, project_rules,
                              run_analysis)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")
LINTED_PATHS = [os.path.join(REPO, d)
                for d in ("cpd_tpu", "tests", "tools", "examples")]
RULE_IDS = sorted(all_rules())
# the AST-scope rules: their fixtures are lint_file-able source pairs.
# Program-scope (ir-*) fixtures are REGISTRIES of traced jax programs,
# exercised by tests/test_analysis_ir.py instead.
AST_RULE_IDS = sorted(set(RULE_IDS) - set(program_rules()))


def _fixture(rule_id: str, kind: str) -> str:
    return os.path.join(FIXTURES, f"{rule_id.replace('-', '_')}_{kind}.py")


def test_catalog_is_complete():
    assert RULE_IDS == ["axis-flow", "axis-name", "collective-contract",
                        "compat-drift", "donation", "format-bounds",
                        "format-flow", "host-clock", "host-leak",
                        "host-race", "host-unbounded", "ir-bitwise",
                        "ir-overlap", "ir-retrace", "ir-schedule",
                        "ir-trace", "ir-wire-ledger", "jit-hazards",
                        "kahan-ordering", "obs-print", "pallas-hygiene",
                        "retrace", "swallow"]


def test_scope_split():
    assert sorted(project_rules()) == ["axis-flow", "collective-contract",
                                       "format-flow", "retrace"]
    assert sorted(program_rules()) == ["ir-bitwise", "ir-overlap",
                                       "ir-retrace", "ir-schedule",
                                       "ir-trace", "ir-wire-ledger"]
    assert sorted(host_rules()) == ["host-clock", "host-leak",
                                    "host-race", "host-unbounded"]
    assert (set(module_rules()) | set(project_rules())
            | set(program_rules()) | set(host_rules())) == set(RULE_IDS)


@pytest.mark.parametrize("rule_id", AST_RULE_IDS)
def test_bad_fixture_is_a_true_positive(rule_id):
    findings = lint_file(_fixture(rule_id, "bad"), select=[rule_id])
    assert findings, f"{rule_id}: bad fixture produced no findings"
    assert all(f.rule == rule_id for f in findings)


@pytest.mark.parametrize("rule_id", AST_RULE_IDS)
def test_good_fixture_is_a_true_negative(rule_id):
    # clean under the WHOLE catalog, not just its own rule
    findings = lint_file(_fixture(rule_id, "good"))
    assert findings == [], (
        f"{rule_id}: good fixture tripped "
        f"{[(f.rule, f.line, f.message) for f in findings]}")


def test_every_program_rule_has_fixture_registry_files():
    """ir-* fixtures are registries of real traced programs; their
    pinned true-positive counts live in tests/test_analysis_ir.py —
    here we only pin that BOTH halves exist for every program rule so
    a new rule cannot land exampleless (and --explain stays useful)."""
    for rule_id in sorted(program_rules()):
        for kind in ("bad", "good"):
            assert os.path.isfile(_fixture(rule_id, kind)), (
                f"{rule_id}: missing {kind} fixture registry")


def test_bad_fixture_finding_counts():
    """Each bad fixture encodes a known number of defects; pin them so a
    rule silently losing a check fails loudly."""
    expected = {"format-bounds": 6, "axis-name": 2, "jit-hazards": 6,
                "pallas-hygiene": 5, "kahan-ordering": 3, "donation": 2,
                "swallow": 4,
                # v2 (whole-program + compat inventory) rules
                "format-flow": 7, "axis-flow": 2,
                "collective-contract": 4, "retrace": 7,
                "compat-drift": 5,
                # ISSUE 11: ad-hoc stdout telemetry bypassing the obs
                # MetricsRegistry
                "obs-print": 3,
                # v4 host-runtime contracts (per-class dataflow over
                # long-lived serving/fleet/obs objects — ISSUE 16)
                "host-race": 3, "host-unbounded": 4, "host-leak": 5,
                "host-clock": 4}
    # program-scope (ir-*) counts are pinned in tests/test_analysis_ir.py
    # against their fixture REGISTRIES, not lint_file-able sources
    assert set(expected) == set(AST_RULE_IDS), \
        "new AST rule missing a count pin"
    for rule_id, n in expected.items():
        findings = lint_file(_fixture(rule_id, "bad"), select=[rule_id])
        assert len(findings) == n, (
            f"{rule_id}: expected {n} findings, got "
            f"{[(f.line, f.message) for f in findings]}")


def test_retrace_bad_fixture_covers_the_pr5_bug_class():
    """The distilled pre-fix CLI shape — a StepTable keyed by the bare
    transport mode while a PrecisionSupervisor escalates formats — must
    be one of the retrace fixture's findings."""
    findings = lint_file(_fixture("retrace", "bad"), select=["retrace"])
    assert any("ladder_step_key" in f.message for f in findings), \
        [f.message for f in findings]


# ---------------------------------------------------------------------------
# suppression grammar
# ---------------------------------------------------------------------------

_BAD_LINE = "from cpd_tpu.quant.numerics import cast_to_format\n" \
            "y = cast_to_format(x, 9, 2)"


def test_line_suppression():
    src = _BAD_LINE + "  # cpd: disable=format-bounds — testing\n"
    assert lint_source(src) == []


def test_line_suppression_ascii_justification():
    # ASCII separators must work too, not just the em-dash
    for sep in ("-- known-bad fixture", "because reasons"):
        src = _BAD_LINE + f"  # cpd: disable=format-bounds {sep}\n"
        assert lint_source(src) == [], sep


def test_line_suppression_is_rule_scoped():
    src = _BAD_LINE + "  # cpd: disable=axis-name\n"
    assert [f.rule for f in lint_source(src)] == ["format-bounds"]


def test_file_suppression():
    src = "# cpd: disable-file=format-bounds\n" + _BAD_LINE + "\n"
    assert lint_source(src) == []


def test_skip_file():
    src = "# cpd: skip-file\n" + _BAD_LINE + "\n"
    assert lint_source(src) == []


def test_unsuppressed_fires():
    assert [f.rule for f in lint_source(_BAD_LINE + "\n")] \
        == ["format-bounds"]


def test_suppressions_survive_project_rules():
    """Project-scoped findings honor the same # cpd: directives."""
    src = ("import jax\n"
           "def loop(f, xs):\n"
           "    for x in xs:\n"
           "        y = jax.jit(f)(x)  # cpd: disable=retrace — demo\n"
           "    return y\n")
    assert lint_source(src) == []
    assert [f.rule for f in lint_source(src.replace(
        "  # cpd: disable=retrace — demo", ""))] == ["retrace"]


def test_swallow_rule_exempts_resilience_package_via_config():
    """The resilience/ carve-out moved from rule code into CONFIG
    (built-in defaults mirror pyproject's [tool.cpd-lint.exempt]): the
    same source flags everywhere else but is silent there."""
    src = "try:\n    x = 1\nexcept Exception:\n    pass\n"
    assert [f.rule for f in lint_source(
        src, path="cpd_tpu/utils/helper.py")] == ["swallow"]
    assert lint_source(
        src, path="cpd_tpu/resilience/loop.py") == []


def test_directives_in_docstrings_are_inert():
    # the docstring MENTIONS skip-file/disable; only real comments count
    src = ('"""Docs: use `# cpd: skip-file` or `# cpd: '
           'disable-file=format-bounds`."""\n') + _BAD_LINE + "\n"
    assert [f.rule for f in lint_source(src)] == ["format-bounds"]


def test_statement_first_line_suppression_covers_multiline_call():
    src = ("from cpd_tpu.quant.numerics import cast_to_format\n"
           "y = cast_to_format(  # cpd: disable=format-bounds — testing\n"
           "    x, 9, 2)\n")
    assert lint_source(src) == []


# ---------------------------------------------------------------------------
# the whole-program layer: cross-file propagation
# ---------------------------------------------------------------------------

def _write_tree(tmp_path, files: dict) -> str:
    root = tmp_path / "proj"
    root.mkdir(parents=True, exist_ok=True)
    for rel, body in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    return str(root)


def test_axis_flow_crosses_files(tmp_path):
    """The exact hole the v1 axis-name exemption left open: a library
    module with a hardcoded axis is judged by its CALLERS' meshes — a
    caller binding the axis keeps it clean, no caller anywhere flags."""
    lib = """
        from jax import lax

        def library_reduce(x):
            return lax.psum(x, "dp")
    """
    good_driver = """
        import jax
        from jax.sharding import Mesh
        from lib import library_reduce

        def driver(x):
            mesh = Mesh(jax.devices(), ("dp",))
            with mesh:
                return library_reduce(x)
    """
    root = _write_tree(tmp_path, {"lib.py": lib,
                                  "driver.py": good_driver})
    assert [f for f in lint_tree([root], select=["axis-flow"])] == []

    # same library, caller binds only "tp": now nothing reaches "dp"
    root2 = _write_tree(tmp_path / "2", {
        "lib.py": lib,
        "driver.py": good_driver.replace('("dp",)', '("tp",)')})
    findings = lint_tree([root2], select=["axis-flow"])
    assert [f.rule for f in findings] == ["axis-flow"]
    assert findings[0].path.endswith("lib.py")


def test_format_flow_ladder_crosses_files(tmp_path):
    """A man<2 ladder rung constructed in one file must be caught when
    the ring sink sits two calls away in another file."""
    lib = """
        def reduce_with(grads, mode):
            from cpd_tpu.parallel.dist import sum_gradients
            return sum_gradients(grads, "dp", mode=mode)

        def guarded(grads, ladder):
            return reduce_with(grads, mode="ring")
    """
    cli = """
        from lib import guarded

        def main(grads):
            return guarded(grads, ladder="e5m2,e8m1")
    """
    root = _write_tree(tmp_path, {"lib.py": lib, "cli.py": cli})
    findings = lint_tree([root], select=["format-flow"])
    assert [f.rule for f in findings] == ["format-flow"]
    assert findings[0].path.endswith("cli.py")
    assert "e8m1" in findings[0].message

    # widen the rung: clean
    root2 = _write_tree(tmp_path / "2", {
        "lib.py": lib,
        "cli.py": cli.replace("e5m2,e8m1", "e5m2,e8m10")})
    assert lint_tree([root2], select=["format-flow"]) == []


def test_format_flow_block_drift_crosses_files(tmp_path):
    """A block-scaled wire packed in one file and unpacked at a
    different block size in another is a finding (the ("packed", fmt,
    block) lattice value survives the call boundary); the matching
    pair is clean."""
    lib = """
        from cpd_tpu.quant.numerics import pack_exmy_blocked

        def make_wire(x):
            return pack_exmy_blocked(x, 4, 3, 128)
    """
    cli = """
        from lib import make_wire
        from cpd_tpu.quant.numerics import unpack_exmy_blocked

        def restore(x, n):
            return unpack_exmy_blocked(make_wire(x), 4, 3, n, 64)
    """
    root = _write_tree(tmp_path, {"lib.py": lib, "cli.py": cli})
    findings = lint_tree([root], select=["format-flow"])
    assert [f.rule for f in findings] == ["format-flow"]
    assert findings[0].path.endswith("cli.py")
    assert "block" in findings[0].message

    root2 = _write_tree(tmp_path / "2", {
        "lib.py": lib, "cli.py": cli.replace("n, 64", "n, 128")})
    assert lint_tree([root2], select=["format-flow"]) == []


def test_format_flow_covers_zero_and_kvcache_style_sites(tmp_path):
    """ISSUE 12 satellite: the ("packed", fmt, block) lattice covers the
    NEW blocked-wire owners — a ZeRO-2-style all_to_all module whose
    pack/unpack block sizes drift, and a kvcache-style module that
    decodes a blocked page with the per-tensor unpacker (dropping every
    block's 2^k scale).  Matching pairs are clean — which is exactly
    what pins the live zero.py/kvcache.py sites."""
    zero_like = """
        from cpd_tpu.quant.numerics import (pack_exmy_blocked,
                                            unpack_exmy_blocked)

        def reduce_scatter(payload, c):
            wire = pack_exmy_blocked(payload, 4, 3, 32)
            # the all_to_all would ride here; receiver unpacks at a
            # DIFFERENT block size — every element lands on the wrong
            # block's scale
            return unpack_exmy_blocked(wire, 4, 3, c, 16)
    """
    kv_like = """
        from cpd_tpu.quant.numerics import (pack_exmy_blocked,
                                            unpack_exmy)

        def gather_page(rows):
            packed = pack_exmy_blocked(rows, 4, 3, 32)
            # per-tensor unpack of a blocked page: the shift sidecar is
            # read as code bytes and every block's scale is dropped
            return unpack_exmy(packed, 4, 3)
    """
    root = _write_tree(tmp_path, {"zero_like.py": zero_like,
                                  "kv_like.py": kv_like})
    findings = lint_tree([root], select=["format-flow"])
    assert sorted(f.path.rsplit("/", 1)[-1] for f in findings) == \
        ["kv_like.py", "zero_like.py"], findings
    root2 = _write_tree(tmp_path / "2", {
        "zero_like.py": zero_like.replace("c, 16", "c, 32"),
        "kv_like.py": kv_like.replace(
            "unpack_exmy)", "unpack_exmy_blocked)").replace(
            "unpack_exmy(packed, 4, 3)",
            "unpack_exmy_blocked(packed, 4, 3, rows.shape[-1], 32)")})
    assert lint_tree([root2], select=["format-flow"]) == []


# ---------------------------------------------------------------------------
# the fingerprint cache
# ---------------------------------------------------------------------------

def test_cache_warm_run_reparses_nothing_and_edits_invalidate(tmp_path):
    src_dir = _write_tree(tmp_path, {
        "a.py": "x = 1\n",
        "b.py": _BAD_LINE + "\n",
    })
    cache_dir = str(tmp_path / "cache")

    cold = run_analysis([src_dir], cache_dir=cache_dir)
    assert cold.files_checked == 2
    assert cold.files_parsed == 2
    assert [f.rule for f in cold.findings] == ["format-bounds"]

    warm = run_analysis([src_dir], cache_dir=cache_dir)
    assert warm.files_checked == 2
    assert warm.files_parsed == 0, "warm unchanged tree must re-parse 0"
    assert warm.findings == cold.findings

    # edit a file -> exactly its entry is stale
    bad = os.path.join(src_dir, "b.py")
    with open(bad, "a") as fh:
        fh.write("z = cast_to_format(x, 9, 3)\n")
    os.utime(bad, (os.path.getmtime(bad) + 2,) * 2)
    third = run_analysis([src_dir], cache_dir=cache_dir)
    assert third.files_parsed == 1
    assert len(third.findings) == 2

    # --no-cache bypasses entirely
    nocache = run_analysis([src_dir], use_cache=False)
    assert nocache.files_parsed == 2


def test_cache_select_run_does_not_poison_full_run(tmp_path):
    src_dir = _write_tree(tmp_path, {"b.py": _BAD_LINE + "\n"})
    cache_dir = str(tmp_path / "cache")
    first = run_analysis([src_dir], select=["axis-name"],
                         cache_dir=cache_dir)
    assert first.findings == []
    full = run_analysis([src_dir], cache_dir=cache_dir)
    assert [f.rule for f in full.findings] == ["format-bounds"]
    assert full.files_parsed == 0      # served from cache, unpoisoned


def test_cache_config_edit_invalidates_warm_run(tmp_path):
    """ISSUE 14 satellite: the resolved [tool.cpd-lint] config is part
    of the cache fingerprint — editing pyproject re-runs the affected
    rules on a warm cache instead of silently serving verdicts keyed
    under the old policy."""
    src_dir = _write_tree(tmp_path, {"b.py": _BAD_LINE + "\n"})
    pyproject = tmp_path / "pyproject.toml"
    pyproject.write_text('[tool.cpd-lint.exempt]\n'
                         '"format-bounds" = ["b.py"]\n')
    cache_dir = str(tmp_path / "cache")

    cold = run_analysis([src_dir], cache_dir=cache_dir)
    assert cold.findings == []          # exempted by config
    assert cold.files_parsed == 1
    warm = run_analysis([src_dir], cache_dir=cache_dir)
    assert warm.files_parsed == 0

    # config edit: drop the exemption — the warm cache must invalidate
    # and the finding must surface on the very next run
    pyproject.write_text('[tool.cpd-lint.exempt]\n'
                         '"format-bounds" = ["elsewhere/"]\n')
    third = run_analysis([src_dir], cache_dir=cache_dir)
    assert third.files_parsed == 1, \
        "config edit must invalidate the warm cache"
    assert [f.rule for f in third.findings] == ["format-bounds"]

    # and the new policy's cache is itself warm afterwards
    fourth = run_analysis([src_dir], cache_dir=cache_dir)
    assert fourth.files_parsed == 0
    assert [f.rule for f in fourth.findings] == ["format-bounds"]


# ---------------------------------------------------------------------------
# config: [tool.cpd-lint] precedence
# ---------------------------------------------------------------------------

_SWALLOW = "try:\n    x = 1\nexcept Exception:\n    pass\n"


def test_pyproject_table_overrides_builtin(tmp_path):
    src_dir = _write_tree(tmp_path, {
        "resilience/loop.py": _SWALLOW,
        "pyproject.toml": """
            [tool.cpd-lint]
            [tool.cpd-lint.exempt]
            swallow = ["nothing-matches-this/"]
        """,
    })
    # discovered pyproject REPLACES the built-in exempt table: the
    # resilience/ carve-out is gone, the handler flags
    findings = run_analysis([src_dir], use_cache=False).findings
    assert [f.rule for f in findings] == ["swallow"]


def test_cli_config_overrides_pyproject(tmp_path):
    src_dir = _write_tree(tmp_path, {
        "resilience/loop.py": _SWALLOW,
        "pyproject.toml": """
            [tool.cpd-lint]
            [tool.cpd-lint.exempt]
            swallow = ["nothing-matches-this/"]
        """,
        "override.toml": """
            [tool.cpd-lint]
            [tool.cpd-lint.exempt]
            swallow = ["resilience/"]
        """,
    })
    res = run_analysis([src_dir], use_cache=False,
                       config_path=os.path.join(src_dir, "override.toml"))
    assert res.findings == []
    assert res.config.source.endswith("override.toml")


def test_cli_config_layers_per_key_over_pyproject(tmp_path):
    """Precedence is PER KEY: a --config that sets only `paths` still
    takes its exempt table from the discovered pyproject."""
    src_dir = _write_tree(tmp_path, {
        "resilience/loop.py": _SWALLOW,
        "pyproject.toml": """
            [tool.cpd-lint]
            [tool.cpd-lint.exempt]
            swallow = ["resilience/"]
        """,
        "paths-only.toml": """
            [tool.cpd-lint]
            paths = ["resilience"]
        """,
    })
    res = run_analysis([src_dir], use_cache=False,
                       config_path=os.path.join(src_dir,
                                                "paths-only.toml"))
    assert res.findings == []          # pyproject's exempt still applies


def test_unsupported_syntax_inside_cpd_lint_table_is_loud(tmp_path):
    """A dotted key INSIDE [tool.cpd-lint] must be exit-2, not a
    silently dropped exemption; the same syntax elsewhere in pyproject
    is tolerated."""
    from cpd_tpu.analysis.config import ConfigError, parse_toml_subset
    parse_toml_subset("[tool.other]\nexempt.swallow = 1\n")  # tolerated
    with pytest.raises(ConfigError):
        parse_toml_subset("[tool.cpd-lint]\n"
                          'exempt.swallow = ["resilience/"]\n')


def test_duplicate_stem_scripts_keep_their_own_findings(tmp_path):
    """Two scripts named train.py must each be analyzed, with findings
    attributed to the right file (the graph de-collides same-stem
    modules)."""
    bad_loop = """
        import jax

        def run(f, xs):
            while xs:
                y = jax.jit(f)(xs.pop())
            return y
    """
    root = _write_tree(tmp_path, {
        "a/train.py": bad_loop,
        "b/train.py": bad_loop.replace("def run", "def other_run"),
    })
    findings = lint_tree([root], select=["retrace"])
    assert len(findings) == 2
    assert {os.path.basename(os.path.dirname(f.path))
            for f in findings} == {"a", "b"}


def test_negated_stride_perm_flags_without_crashing():
    """`(c - 2*i) % w` is as non-injective as `2*i` — and must be a
    finding, not a TypeError inside the comprehension classifier."""
    src = ("from jax import lax\n"
           "def f(x, w, c):\n"
           "    perm = [((c - 2 * i) % w, i) for i in range(w)]\n"
           "    return lax.ppermute(x, 'dp', perm)\n")
    findings = lint_source(src, select=["collective-contract"])
    assert [f.rule for f in findings] == ["collective-contract"]


def test_axis_flow_stays_silent_without_callers(tmp_path):
    """Under a partial graph (--changed-only lints one file) the
    binding driver may be outside the analyzed set: no callers means no
    verdict — the full-tree gate is where absence convicts."""
    lib = """
        from jax import lax

        def library_reduce(x):
            return lax.psum(x, "dp")
    """
    root = _write_tree(tmp_path, {"lib.py": lib})
    assert lint_tree([root], select=["axis-flow"]) == []


def test_shipped_pyproject_carries_the_carveouts():
    """The defaults moved INTO pyproject (the point of the satellite):
    the shipped [tool.cpd-lint] table must keep the swallow/resilience
    and compat-drift/compat.py carve-outs."""
    from cpd_tpu.analysis.config import load_config
    cfg = load_config([REPO])
    assert cfg.source.endswith("pyproject.toml")
    assert "cpd_tpu/resilience/" in cfg.exempt.get("swallow", ())
    assert "cpd_tpu/compat.py" in cfg.exempt.get("compat-drift", ())


# ---------------------------------------------------------------------------
# the live tree is clean — THE gate
# ---------------------------------------------------------------------------

def test_live_tree_is_clean():
    findings = lint_tree(LINTED_PATHS)
    assert findings == [], (
        "lint regressions in the live tree:\n" + "\n".join(
            f"{f.path}:{f.line}: {f.rule}: {f.message}"
            for f in findings))


def test_compat_drift_inventory_is_empty_outside_compat():
    """ROADMAP item 5 precondition, machine-checked: zero unsuppressed
    jax.experimental/removed-API uses outside cpd_tpu/compat.py."""
    findings = lint_tree(LINTED_PATHS, select=["compat-drift"])
    assert findings == [], [(f.path, f.line) for f in findings]


def test_live_suppression_count_is_pinned():
    """Suppressions are reviewed claims.  Every `# cpd: disable` comment
    in the live tree must carry a written justification — on the
    directive itself, or as a comment on the immediately preceding
    line(s) — and the total is pinned: a new suppression is a
    deliberate, counted decision, not an escape hatch.  Directives are
    read from real COMMENT tokens (a test that embeds the syntax in a
    string literal does not count)."""
    import io
    import tokenize
    pat = re.compile(r"cpd:\s*disable(?:-file)?=([A-Za-z0-9_,\- ]+)")
    sites = []
    for root in LINTED_PATHS:
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", "fixtures")
                           and not d.startswith(".")]
            for fn in filenames:
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path, encoding="utf-8") as fh:
                    src = fh.read()
                lines = src.splitlines()
                for tok in tokenize.generate_tokens(
                        io.StringIO(src).readline):
                    if tok.type != tokenize.COMMENT:
                        continue
                    m = pat.search(tok.string)
                    if not m:
                        continue
                    payload = m.group(1).strip()
                    # justification: text beyond the rule list (inside
                    # the captured payload, or after it — em-dash
                    # separators end the capture), or a comment on one
                    # of the two preceding lines
                    inline = bool(re.search(r"[A-Za-z0-9_-]+\s+\S",
                                            payload)
                                  or tok.string[m.end():].strip())
                    above = any(
                        lines[i].lstrip().startswith("#")
                        for i in range(max(0, tok.start[0] - 3),
                                       tok.start[0] - 1))
                    assert inline or above, (
                        f"{path}:{tok.start[0]}: suppression without a "
                        f"written justification: {payload!r}")
                    sites.append((path, tok.start[0], payload))
    # 8 pre-v4 + 6 host-unbounded claims added with the host scope
    # (ISSUE 16): Injector.fired/log (bounded by the fault plan),
    # StepTable._cache (static level vocabulary), MetricsRegistry
    # ._metrics (declared-name cardinality), ServeEngine.logits_log
    # (tests-only oracle tap), TSVLogger.log (one line per epoch — the
    # DAWNBench artifact itself); minus the 2 `swallow` claims that left
    # utils/cache.py with its CPUID tag and its catch-all (PR 21)
    assert len(sites) == 12, (
        "live-tree suppression count changed — review the new/removed "
        "site's justification and re-pin:\n" + "\n".join(
            f"{p}:{ln}: {pl}" for p, ln, pl in sites))


# ---------------------------------------------------------------------------
# CLI exit-code contract (0/1/2) + JSON/SARIF shapes + --explain
# ---------------------------------------------------------------------------

def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "cpd_tpu.analysis", "--no-cache", *args],
        capture_output=True, text=True, cwd=REPO, timeout=180)


def test_cli_exit_0_on_clean():
    proc = _run_cli(_fixture("format-bounds", "good"))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_exit_1_on_findings_and_json_shape():
    proc = _run_cli("--format=json", _fixture("format-bounds", "bad"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["version"] == 1
    assert payload["files_checked"] == 1
    assert payload["counts"]["format-bounds"] == len(payload["findings"])
    f = payload["findings"][0]
    assert set(f) == {"path", "line", "col", "rule", "message"}


def test_cli_sarif_shape():
    proc = _run_cli("--format=sarif", _fixture("format-bounds", "bad"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in doc["$schema"]
    (run,) = doc["runs"]
    assert run["tool"]["driver"]["name"] == "cpd-lint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert rule_ids == set(RULE_IDS)
    assert run["results"], "findings must appear as results"
    res = run["results"][0]
    assert res["ruleId"] == "format-bounds"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("format_bounds_bad.py")
    assert loc["region"]["startLine"] >= 1


def test_cli_exit_2_on_internal_error():
    assert _run_cli("/nonexistent/path_for_lint").returncode == 2
    assert _run_cli("--select=not-a-rule", "cpd_tpu").returncode == 2
    # one good root must not mask a vanished one (coverage shrink)
    assert _run_cli("cpd_tpu", "/nonexistent/path_for_lint").returncode == 2
    # an unreadable --config is an internal error, not silence
    assert _run_cli("--config", "/nonexistent/cpd-lint.toml",
                    "cpd_tpu").returncode == 2


def test_cli_list_rules():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    for rule_id in RULE_IDS:
        assert rule_id in proc.stdout


def test_cli_explain():
    for rule_id in ("retrace", "format-bounds"):
        proc = _run_cli("--explain", rule_id)
        assert proc.returncode == 0, proc.stderr
        assert rule_id in proc.stdout
        # catalog entry + both fixture halves
        assert "FIRES on" in proc.stdout
        assert "stays SILENT on" in proc.stdout
    assert _run_cli("--explain", "not-a-rule").returncode == 2


def test_cli_changed_only_outside_git_is_exit_2(tmp_path):
    src = tmp_path / "x.py"
    src.write_text("x = 1\n")
    if shutil.which("git") is None:
        pytest.skip("no git in environment")
    proc = subprocess.run(
        [sys.executable, "-m", "cpd_tpu.analysis", "--no-cache",
         "--changed-only", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env={**os.environ, "GIT_DIR": str(tmp_path / "nope")})
    assert proc.returncode == 2, proc.stdout + proc.stderr
