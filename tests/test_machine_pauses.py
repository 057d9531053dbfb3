"""``tools/machine_pauses.py``: the watcher beside a command notes the
sleeps that overran and hands the command's exit code on."""

import json
import sys

from tools import machine_pauses


def test_it_hands_on_the_exit_code_and_writes_the_pauses(tmp_path):
    out = tmp_path / "pauses.json"
    rc = machine_pauses.main(["--out", str(out), "--", sys.executable, "-c",
                              "import sys; sys.exit(3)"])
    got = json.loads(out.read_text())
    assert rc == 3
    assert got["command"][1:] == ["-c", "import sys; sys.exit(3)"]
    assert isinstance(got["pauses"], list) and got["seconds"] >= 0


def test_a_sleep_that_overran_is_a_pause(monkeypatch):
    clock = iter([0.0, 0.005, 0.125, 0.130])      # the second sleep: 120 ms

    class Child:
        polls = 0

        def poll(self):
            self.polls += 1
            return None if self.polls <= 3 else 0

    monkeypatch.setattr(machine_pauses.time, "perf_counter",
                        lambda: next(clock))
    monkeypatch.setattr(machine_pauses.time, "sleep", lambda s: None)
    assert machine_pauses.watch(Child(), 0.03) == [(0.005, 120.0)]
