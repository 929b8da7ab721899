"""The rest of a run with the harness's look for a chip skipped (the
rehearsal option): a sound program reads `correct` true; the control in
the program's place, and an answer altered where it is produced, read
false."""

import json

import pytest

import run


def _result(capsys, *extra):
    rc = run.main(["--workload", "sf1-q6", "--seed", str(2**31 + 11),
                   "--seconds", "1", "--trace", "0", "--sf", "0.02",
                   "--rehearse-cpu", "1", *extra])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]


def test_a_sound_run_is_correct_and_its_control_is_caught(capsys):
    result, notes = _result(capsys, "--control", "1")
    assert result["correct"] is True and result["rehearsal"] is True
    assert list(result)[-1] == "compared"
    assert result["compared"]["stmts_wrong"] == {"value": 0, "limit": 0}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"stmt_p50_ms", "stmts_per_s", "rows_per_s", "setup_s"} <= set(
        result["metrics"])
    control = [n["control"] for n in notes if "control" in n][0]
    assert control["caught"] is True
    assert control["numbers"]["stmts_wrong"]["value"] == result["attempted"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    """The program's session returns Q6's sum one unit of the last
    decimal place too high, every other statement; the wire, the client
    and the comparison see it."""
    from decimal import Decimal

    from tidb_tpu.session.session import Session

    sound = Session.execute
    state = {"n": 0}

    def faulty(self, sql, *a, **k):
        out = sound(self, sql, *a, **k)
        if sql.lstrip().lower().startswith("select sum("):
            state["n"] += 1
            rs = out[-1]
            if state["n"] % 2 == 0 and rs.rows and rs.rows[0][0] is not None:
                rs.rows[0] = (Decimal(str(rs.rows[0][0]))
                              + Decimal("0.0001"),) + tuple(rs.rows[0][1:])
        return out

    monkeypatch.setattr(Session, "execute", faulty)
    result, _ = _result(capsys)
    assert result["correct"] is False
    assert result["compared"]["stmts_wrong"]["value"] > 0


def test_no_tpu_no_result(capsys):
    rc = run.main(["--workload", "sf1-q6", "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"])
