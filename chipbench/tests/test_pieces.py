"""What a configuration names is found by file: a missing key or file fails
with its name; every comparison's result is held to one contract; and a
second reference, comparison and warm-up laid in a temporary directory are
found and run with no harness file touched.  No test here needs JAX or the
program: the pieces a test writes are plain Python over a stand-in engine."""

import hashlib
import json
import os
import textwrap
import types

import pytest

from chipbench import check, pieces, readers, run, serve_child

NAMES = {"reference": "ref_a", "comparison": "cmp_a", "warmups": ["warm_a"]}


def _lay(root, kind, name, code):
    os.makedirs(os.path.join(root, kind), exist_ok=True)
    with open(os.path.join(root, kind, name + ".py"), "w") as f:
        f.write(textwrap.dedent(code))


def _lay_named(root, but=None):
    """A file for every piece NAMES names, but those of the key `but`."""
    for key, kind in pieces.CONFIG_PIECES.items():
        if key != but:
            for name in ([NAMES[key]] if isinstance(NAMES[key], str)
                         else NAMES[key]):
                _lay(root, kind, name, "X = 1\n")


# -- no default: a missing key or file fails with its name -------------------

@pytest.mark.parametrize("key", sorted(pieces.CONFIG_PIECES))
def test_a_configuration_that_names_no_piece_fails_with_the_key(key, tmp_path):
    _lay_named(str(tmp_path))
    assert pieces.named(NAMES, str(tmp_path)) == NAMES
    cfg = {k: v for k, v in NAMES.items() if k != key}
    with pytest.raises(pieces.MissingPiece, match=repr(key)):
        pieces.named(cfg, str(tmp_path))
    # Nothing, or a list where one name belongs and the other way round.
    other = NAMES["warmups" if key != "warmups" else "reference"]
    for empty in ("", [], None, other):
        with pytest.raises(pieces.MissingPiece, match=repr(key)):
            pieces.named(dict(cfg, **{key: empty}), str(tmp_path))


@pytest.mark.parametrize("key", sorted(pieces.CONFIG_PIECES))
def test_a_named_file_that_is_not_there_fails_with_its_name(key, tmp_path):
    root = str(tmp_path)
    _lay_named(root, but=key)
    gone = NAMES[key] if isinstance(NAMES[key], str) else NAMES[key][0]
    with pytest.raises(pieces.MissingPiece, match=gone):
        pieces.named(NAMES, root)
    with pytest.raises(pieces.MissingPiece, match=gone):
        pieces.load(pieces.CONFIG_PIECES[key], gone, root)


def test_a_file_without_what_the_harness_calls_fails_with_both_names(tmp_path):
    _lay(str(tmp_path), "warmups", "half", "STEP_PROGRAMS = True\n")
    with pytest.raises(pieces.MissingPiece, match="half.py defines no warm"):
        pieces.load("warmups", "half", str(tmp_path),
                    needs=("warm", "STEP_PROGRAMS"))


def test_a_cell_whose_configuration_names_nothing_fails_before_any_process(
        monkeypatch):
    """`run.load_cell` is the first thing a run does."""
    cell = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))[
        "workloads"][0]["name"]
    run.load_cell(cell)
    monkeypatch.setattr(pieces, "CONFIG_PIECES",
                        dict(pieces.CONFIG_PIECES, a_new_piece="nowhere"))
    with pytest.raises(run.BenchFailure, match="a_new_piece"):
        run.load_cell(cell)


# -- the contract of a comparison's result ------------------------------------

def _sound(n=3):
    return {"ok": True, "problems": [], "prompts": n, "compared": n,
            "limits": [{"name": "gap", "value": 0.01, "limit": 0.09},
                       {"name": "exact", "value": 0, "limit": 0}],
            "rows": ["kept"]}


def test_a_sound_result_passes_and_keeps_what_else_it_reports():
    out = check.hold(_sound(), 3)
    assert out["ok"] is True and out["problems"] == []
    assert out["rows"] == ["kept"]


@pytest.mark.parametrize("spoil,says", [
    (lambda r: r.update(compared=2), "2 of 3 prompts compared"),
    (lambda r: r.update(prompts=2, compared=2), "asked to compare 3"),
    (lambda r: r["limits"][0].update(value=0.1), "gap: 0.1 is over its limit"),
    (lambda r: r.update(limits=[]), "names no limit"),
    (lambda r: r.pop("limits"), "names no limit"),
    (lambda r: r["limits"][0].update(value=float("nan")), "not finite"),
    (lambda r: r["limits"][0].update(limit=float("inf")), "not finite"),
    (lambda r: r["limits"][0].pop("limit"), "without name, value and limit"),
    (lambda r: r.update(problems=["one row was odd"]), "one row was odd"),
    (lambda r: r.pop("ok"), "neither ok nor why not"),
    (lambda r: r.pop("compared"), "None of 3 prompts compared"),
], ids=["fewer-compared", "fewer-asked", "over-its-limit", "empty-limits",
        "no-limits", "nan", "infinite-limit", "limit-missing",
        "ok-beside-problems", "no-ok", "no-count"])
def test_a_result_the_contract_does_not_bear_out_is_not_ok(spoil, says):
    result = _sound()
    spoil(result)
    out = check.hold(result, 3)
    assert out["ok"] is False
    assert any(says in p for p in out["problems"]), out["problems"]


def test_a_result_that_is_no_dict_is_not_ok():
    out = check.hold(None, 3)
    assert out["ok"] is False and "NoneType" in out["problems"][0]


def test_a_result_that_says_not_ok_stays_so_with_its_reasons():
    out = check.hold(dict(_sound(), ok=False, problems=["a", "b"]), 3)
    assert out["ok"] is False and out["problems"] == ["a", "b"]


# -- files only: a second set of pieces, found by name ------------------------

REFERENCE = '''
    """A stand-in block: the logit of token t is the sum of the ids so far."""
    def forward(hf, params, tokens, **kw):
        out, total = [], 0
        for t in tokens:
            total += t * params["scale"]
            out.append([total + v for v in range(hf["vocab_size"])])
        return out
'''
COMPARISON = '''
    """Compares the stand-in engine's last-row logits with the reference."""
    LIMIT = 0.5        # the stand-in engine is exact; its control is 1 off
    LENGTHS = (2, 3, 5)

    def run(core, hf, seed, lengths, reference):
        worst, rows = 0.0, 0
        for n in lengths:
            prompt = [(seed + i) % 7 + 1 for i in range(n)]
            want = reference.forward(hf, core.params, prompt)[-1]
            got = core.logits(prompt)
            worst = max([worst] + [abs(a - b) for a, b in zip(got, want)])
            rows += 1
        return {"ok": worst <= LIMIT, "prompts": len(lengths),
                "compared": rows,
                "problems": [] if worst <= LIMIT else [f"gap {worst}"],
                "limits": [{"name": "gap", "value": worst, "limit": LIMIT}]}
'''
WARMUP = '''
    """Dispatches one stand-in program a context bucket."""
    STEP_PROGRAMS = %s

    def warm(core, max_context, vocab):
        core.warmed.append((%r, max_context, vocab))
        return {"shapes": max_context // 128, "seconds": %s}
'''


class _Core:
    """A stand-in engine: exact, or off by `err` in every logit."""

    def __init__(self, err=0.0):
        self.params, self.err, self.warmed = {"scale": 2}, err, []

    def logits(self, prompt):
        total = sum(prompt) * self.params["scale"]
        return [total + v + self.err for v in range(8)]


def _tree(root) -> str:
    """A digest of every file under `root`, compiled caches apart."""
    h = hashlib.sha256()
    for d, _dirs, files in sorted(os.walk(root)):
        if "__pycache__" in d:
            continue
        for f in sorted(files):
            with open(os.path.join(d, f), "rb") as fh:
                h.update(os.path.join(d, f).encode() + fh.read())
    return h.hexdigest()


def test_a_second_reference_comparison_and_warmup_are_files_only(tmp_path):
    before = _tree(run.HERE)
    root = str(tmp_path)
    _lay(root, "references", "ref_b", REFERENCE)
    _lay(root, "comparisons", "cmp_b", COMPARISON)
    _lay(root, "warmups", "warm_b1", WARMUP % (True, "b1", 1.5))
    _lay(root, "warmups", "warm_b2", WARMUP % (False, "b2", 0.25))
    _lay(root, "warmups", "warm_b3", WARMUP % (True, "b3", 2.0))
    hf = {"vocab_size": 8, "reference": "ref_b", "comparison": "cmp_b",
          "warmups": ["warm_b3", "warm_b1", "warm_b2"]}
    assert pieces.named(hf, root) == {k: hf[k] for k in pieces.CONFIG_PIECES}

    out = check.run_check(_Core(), hf, 5, root=root)
    assert out["ok"] is True and out["prompts"] == out["compared"] == 3
    assert (out["reference"], out["comparison"]) == ("ref_b", "cmp_b")
    assert out["limits"] == [{"name": "gap", "value": 0.0, "limit": 0.5}]
    # Lengths the harness gives (a rehearsal's) take the place of its own.
    assert check.run_check(_Core(), hf, 5, (4, 4), root=root)["prompts"] == 2
    # Its control: an engine that is 1 off in every logit is not correct.
    bad = check.run_check(_Core(err=1.0), hf, 5, root=root)
    assert bad["ok"] is False and bad["limits"][0]["value"] == 1.0

    core = _Core()
    warm = serve_child.run_warmups(core, hf["warmups"], 4096, 8, root=root)
    assert [w["name"] for w in warm] == hf["warmups"]      # in its order
    assert core.warmed == [("b3", 4096, 8), ("b1", 4096, 8), ("b2", 4096, 8)]
    assert [w["step_programs"] for w in warm] == [True, True, False]
    assert all(w["shapes"] == 32 for w in warm)
    # The layer metric sums the ones that dispatch step programs.
    ctx = types.SimpleNamespace(child={"warmups": warm})
    assert readers.warm_programs_s(ctx) == 3.5
    assert readers.warm_programs_s(types.SimpleNamespace(child={})) is None
    assert _tree(run.HERE) == before       # nothing of the harness was edited


def test_a_warmup_that_returns_no_seconds_stops_the_worker(tmp_path):
    _lay(str(tmp_path), "warmups", "mute", '''
        STEP_PROGRAMS = True

        def warm(core, max_context, vocab):
            return {"shapes": 3}
    ''')
    with pytest.raises(RuntimeError, match="mute.py returned"):
        serve_child.run_warmups(_Core(), ["mute"], 128, 8,
                                root=str(tmp_path))


# -- the moved comparison draws what it drew ----------------------------------

def _comparison():
    with open(os.path.join(run.ROOT, json.load(open(os.path.join(
            run.ROOT, "BENCHMARK.json")))["configs"][0]["file"])) as f:
        return pieces.load("comparisons", json.load(f)["comparison"])


def test_the_moved_comparison_keeps_its_tolerances_and_its_draw():
    import numpy as np

    cmp = _comparison()
    assert (cmp.ATOL_LOGITS, cmp.ATOL_BODY, cmp.MARGIN_LOGITS) \
        == (0.09, 0.02, 0.18)
    assert cmp.LENGTHS == (5, 17, 64, 100, 129, 300, 511, 700)
    assert cmp.DECODE_TOKENS == 9
    for seed in (1, 13, 2147484001 % 2**31):
        was = np.random.default_rng(seed)
        want = [was.integers(1, 32768, size=n).tolist() for n in cmp.LENGTHS]
        got = cmp._prompts(np.random.default_rng(seed), 32768, cmp.LENGTHS, ())
        assert got == want
    reserved = {5, 6, 7}
    got = cmp._prompts(np.random.default_rng(3), 12, (400, 50), reserved)
    assert [len(p) for p in got] == [400, 50]
    assert not reserved & {t for p in got for t in p}
    assert {t for p in got for t in p} == set(range(1, 12)) - reserved
