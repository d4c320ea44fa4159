"""`build/flash_bundles.py` reads the compiler's dumped schedules: its
cutting of a grid loop at the predicated regions, on a hand-made schedule
(the compile itself needs libtpu's dumper and is the helper's own run)."""

import importlib.util
import pathlib

import pytest

HELPER = pathlib.Path(__file__).resolve().parents[1] / "build" / "flash_bundles.py"


@pytest.fixture(scope="module")
def helper():
    spec = importlib.util.spec_from_file_location("flash_bundles", HELPER)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def schedule(pieces) -> str:
    """A final_bundles text: ``pieces`` = [("step", n) | ("when", n, region)]
    inside one loop whose body is itself a predicated region (as Mosaic's
    pipeline wraps a grid step)."""
    lines, n = [], 0

    def bundle(text, label=""):
        nonlocal n
        lines.append(f"{n:#6x} {label + ':' if label else ' '} > {{ {text} }}")
        n += 1

    bundle("%s1 = smov 0")
    bundle("%s2 = sphi %s1", "LB")
    bundle("%9 = sbr.rel (%p1) target bundleno = 999 (0x3e7), region = 90")
    for piece in pieces:
        if piece[0] == "step":
            for _ in range(piece[1]):
                bundle("%v1 = vadd.f32 %v0, %v0")
        else:
            _, size, region = piece
            bundle(f"%8 = sbr.rel (%p2) target bundleno = 1 (0x1), "
                   f"region = {region}")
            for _ in range(size):
                bundle("%v2 = vcmp.ge.s32.totalorder %v0, %v1  ;;  "
                       "%v3 = vsel %vm0, %v0, %v1")
            bundle(f"%s9 = sld [smem:[#x]] }} /* Start/End empty region "
                   f"{region}", "PF")
    bundle("%s3 = sadd.s32 1, %s2 } /* Start/End empty region 90", "PF")
    bundle("%7 = sbr.rel (!%p9) target bundleno = 1 (0x1), region = 99")
    bundle("%s4 = smov 1")
    return "\n".join(lines) + "\n"


def test_cut_finds_the_branches_of_a_grid_step(helper, tmp_path):
    path = tmp_path / "k-71-final_bundles.txt"
    path.write_text(schedule([
        ("step", 5), ("when", 40, 44), ("step", 3), ("when", 100, 48),
        ("when", 8, 50),  # the pipeline's own short branch: folded in
        ("when", 60, 52), ("step", 7),
    ]))
    bundles = helper.read_bundles(str(path))
    pieces = helper.cut_loop(bundles)
    whens = [(b - a + 1) for _, kind, a, b in pieces if kind == "when"]
    assert whens == [40, 100, 60]
    # Everything between the loop's header and its back branch is counted
    # once.
    assert sum(b - a + 1 for _, _, a, b in pieces) == len(bundles) - 3
    # The straight-line part before the first body: the wrapper's branch,
    # five bundles, the branch itself.
    first_when = next(i for i, p in enumerate(pieces) if p[1] == "when")
    assert sum(b - a + 1 for _, _, a, b in pieces[:first_when]) == 2 + 5 + 1


def test_a_kernel_the_call_does_not_run_is_said_to_be_absent(
        helper, tmp_path, capsys):
    """A dump of a call whose backward is the one kernel: the forward and
    `hvt_flash_bwd` are reported, the two-kernel form's names are absent,
    and that is no error. A dump with no kernel at all is a failed compile."""
    assert helper.KERNELS == (
        "hvt_flash_fwd", "hvt_flash_bwd", "hvt_flash_dq", "hvt_flash_dkv")
    helper.report_dump(str(tmp_path))
    assert "no schedule dumped" in capsys.readouterr().out
    for kernel, body in (("hvt_flash_fwd", 40), ("hvt_flash_bwd", 70)):
        stem = tmp_path / f"123-{kernel}.1"
        text = schedule([("step", 5), ("when", body, 44), ("step", 2)])
        (tmp_path / f"{stem.name}-71-final_bundles.txt").write_text(text)
        rows = "\n".join("1 0 2 0 0 0 0" for _ in text.splitlines())
        (tmp_path / f"{stem.name}-69-final-utilization.txt").write_text(
            "slots\nMXU, XLU, VALU, EUP, VLOAD, VLOAD:FILL, VSTORE\n"
            "== UTILIZATION:\n" + rows + "\n")
    helper.report_dump(str(tmp_path))
    out = capsys.readouterr().out
    assert "hvt_flash_fwd.1:" in out and "hvt_flash_bwd.1:" in out
    assert f"{'. . when':<12}{70:>8}" in out
    assert "hvt_flash_dq: absent" in out and "hvt_flash_dkv: absent" in out
    assert "no schedule dumped" not in out
