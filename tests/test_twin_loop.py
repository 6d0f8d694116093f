"""``twin_loop`` fails loudly on the served path: a quarantined event
or a failed bus subscriber turns into a nonzero exit after the report,
except under ``--chaos``, where injected faults are the point."""
import pytest

from repro.core import sync
from repro.launch import twin_loop

ARGS = ["twin_loop", "--trace", "poisson", "--jobs", "6", "--nodes", "4",
        "--backend", "reference", "--no-compile-cache"]


def _run(monkeypatch, extra, fail_at=None):
    if fail_at is not None:
        real, seen = sync.apply_event, [0]

        def flaky(state, ev, **kw):
            seen[0] += 1
            if seen[0] == fail_at:
                raise RuntimeError("injected apply failure")
            return real(state, ev, **kw)
        monkeypatch.setattr(sync, "apply_event", flaky)
    monkeypatch.setattr("sys.argv", ARGS + extra)
    twin_loop.main()


@pytest.mark.parametrize("extra,fail_at,exits", [
    ([], None, False),           # clean run: exit 0
    ([], 5, True),               # a dead letter fails the run
    (["--chaos"], 5, False),     # ... unless faults are injected on purpose
], ids=["clean", "dead_letter", "chaos"])
def test_dead_letters_fail_the_run(monkeypatch, capsys, extra, fail_at,
                                   exits):
    if exits:
        with pytest.raises(SystemExit, match="1 dead letter"):
            _run(monkeypatch, extra, fail_at)
    else:
        _run(monkeypatch, extra, fail_at)
    assert "policy mix" in capsys.readouterr().out
