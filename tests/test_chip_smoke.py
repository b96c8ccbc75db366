"""chip_smoke.py's phases on the CPU mesh at a tiny scale, and its refusals.

The script itself only runs on a TPU; here its phases are imported and driven
at SF0.001 so that a later change which breaks the smoke shows in tier-1, not
on the chip.
"""

from decimal import Decimal

import pytest

import chip_smoke

SCALE = 0.001


def test_load_query_and_compare_on_cpu(capsys):
    # the whole ladder, whatever the chip run drops for time included: the
    # host evaluations stay checked against the engine here
    ladder = chip_smoke.QUERIES + tuple(chip_smoke.DROPPED)
    assert set(ladder) == {"q06", "q01", "q14", "q03", "q18"}
    answers = chip_smoke.one_chip(SCALE, queries=ladder)
    out = capsys.readouterr().out
    assert "load: lineitem rows" in out and "load: region rows 5" in out
    for name in ladder:
        assert f"{name} equal to the host evaluation" in out
    # decimals travel as exact strings, one row for q6, a row per flag pair
    assert len(answers["q06"]) == 1 and isinstance(answers["q06"][0][0], str)
    assert [r[:2] for r in answers["q01"]] == [
        ["A", "F"], ["N", "F"], ["N", "O"], ["R", "F"]
    ]


def test_mesh_phase_on_virtual_devices():
    statements = {"q1_shape": chip_smoke.MESH_STATEMENTS["q1_shape"]}
    answers = chip_smoke.one_chip(SCALE, queries=(), also=statements)
    chip_smoke.mesh(SCALE, 4, statements, answers)
    with pytest.raises(chip_smoke.WrongAnswer, match="mesh q1_shape"):
        chip_smoke.mesh(SCALE, 4, statements, {"q1_shape": []})


def test_main_without_a_tpu_exits_before_loading(monkeypatch, capsys):
    def never(*a, **k):
        raise AssertionError("a phase ran without a TPU")

    for phase in ("start", "load", "host_columns", "one_chip", "run"):
        monkeypatch.setattr(chip_smoke, phase, never)
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""  # no result line


def test_main_has_no_switch_that_proceeds_on_a_cpu():
    for flag in (["--cpu"], ["--force"], ["--platform", "cpu"], ["--scale", "0.5"]):
        with pytest.raises(SystemExit) as e:
            chip_smoke.main(flag)
        assert e.value.code not in (0, None)


def test_a_wrong_expected_answer_fails_the_run(monkeypatch):
    monkeypatch.setitem(
        chip_smoke.EXPECT, "q06", lambda host: [[Decimal("1.0000")]]
    )
    with pytest.raises(chip_smoke.WrongAnswer, match="q06 row 0"):
        chip_smoke.one_chip(SCALE, queries=("q06",))


def test_main_does_not_swallow_a_failed_phase(monkeypatch, capsys):
    def failed(scale):
        raise chip_smoke.WrongAnswer("q06 row 0")

    monkeypatch.setattr(chip_smoke, "require_tpu", chip_smoke.device_facts)
    monkeypatch.setattr(chip_smoke, "run", failed)
    with pytest.raises(chip_smoke.WrongAnswer):
        chip_smoke.main(["--scale", "1"])
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize(
    "got, want, ok",
    [
        ([["1.50"]], [[Decimal("1.5")]], True),
        ([[1.5]], [[Decimal("1.5")]], False),  # a decimal must arrive exact
        ([["1.51"]], [[Decimal("1.5")]], False),
        ([[1.0 + 5e-10]], [[1.0]], True),
        ([[1.0 + 5e-9]], [[1.0]], False),
        ([[3]], [[3]], True),
        ([[3.0]], [[3]], False),
        ([[True]], [[1]], False),
        ([["a", 1]], [["a", 1], ["b", 2]], False),
    ],
)
def test_check_is_exact_for_integers_and_decimals(got, want, ok):
    if ok:
        chip_smoke.check("t", got, want)
    else:
        with pytest.raises(chip_smoke.WrongAnswer):
            chip_smoke.check("t", got, want)
