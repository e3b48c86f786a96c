"""Guard (ISSUE 55): a family's rehearsal calls
``family_contract.rehearse``; no file under ``tests/`` (outside
``tests/perfbench/``, the benchmark's own) spells the
``perfbench.run ... --rehearse`` subprocess itself, so the tenth family
cannot bring a tenth copy of the body.  Pure Python, no jax."""

import pathlib

HERE = pathlib.Path(__file__).parent
HELPER = "family_contract.py"


def spells_the_rehearsal(text: str) -> bool:
    return '"perfbench.run"' in text and '"--rehearse"' in text


def test_only_the_helper_spells_the_rehearsal_subprocess():
    spelled = sorted(p.name for p in HERE.glob("*.py")
                     if p.name != pathlib.Path(__file__).name
                     and spells_the_rehearsal(p.read_text()))
    assert spelled == [HELPER], spelled


def test_every_rehearsal_file_calls_the_helper():
    files = sorted(HERE.glob("test_rehearse_*.py"))
    assert len(files) >= 9
    for path in files:
        text = path.read_text()
        assert "import rehearse" in text and "rehearse(" in text, path.name
        assert "subprocess" not in text and "pytest.mark.slow" not in text, (
            path.name)


def test_the_guard_catches_a_copy():
    copy = ('subprocess.run([sys.executable, "-m", "perfbench.run", '
            '"--workload", CELL, "--rehearse"])')
    assert spells_the_rehearsal(copy)
    assert not spells_the_rehearsal('rehearse("a.cell", 1)')
