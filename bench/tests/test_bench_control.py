"""The control at a size a test run holds: the reference computed in
float8 (the step below the bfloat16 the configuration states), put in
the program's place, reads over the limit that the bf16 program's own
served tokens stay under."""
import json

from bench_tiny import DATA, make_root

from bench import control


def test_control_fails_where_the_program_passes(tmp_path):
    root = make_root(tmp_path)
    limit = json.loads((DATA / "tiny16.json").read_text())[
        "correct"]["max_logit_gap"]
    r = control.readings(root, "tiny16.open", 2**35 + 9, 2.0,
                         require_chip=False)
    assert r["tokens"] >= 10
    assert r["sound"] <= limit < r["control"]
    assert r["sound_correct"] and not r["control_correct"]
