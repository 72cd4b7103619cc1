"""chip_smoke.py's contract where no TPU is attached: it prints the device
line, then refuses, with a nonzero exit and no result line."""
import json

import pytest

import chip_smoke


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_refuses_without_a_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    out, err = capsys.readouterr()
    lines = out.splitlines()
    assert lines[0].startswith("[chip_smoke] a device: platform=cpu")
    assert "no TPU" in err
    for line in lines:
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_diff_keys_names_every_differing_key():
    got = {"cycles": 10, "issued": 4, "stall": 1}
    want = {"cycles": 10, "issued": 5, "l2_hit": 0}
    assert chip_smoke.diff_keys(got, want) == ["issued", "l2_hit", "stall"]
    assert chip_smoke.diff_keys(want, dict(want)) == []
