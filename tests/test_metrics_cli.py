"""CLI-level metrics fixture: all three metrics end-to-end through
scripts/compute_metrics.py.

The LPIPS/FID *math* was tested weight-free, but the weight
LOADING paths (torch.load state dict, torch.jit.load TorchScript) had never
executed.  This fixture checks in that proof: a synthetic AlexNet+LPIPS
state dict and a random-weight TorchScript extractor are written to disk
exactly in the offline artifact formats the CLI documents, two image
directories are generated, and the CLI must print a parseable number for
PSNR, LPIPS, and FID — so the only missing ingredient for published-table
comparability is ever the real weight files (reference computes all three,
/root/reference/scripts/compute_metrics.py:53-79).
"""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

torch = pytest.importorskip("torch")
from PIL import Image

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "scripts", "compute_metrics.py")


def _write_image_dirs(tmp_path, n=6, size=64, seed=0):
    r = np.random.RandomState(seed)
    roots = []
    for j in range(2):
        root = tmp_path / f"imgs{j}"
        root.mkdir()
        roots.append(str(root))
    for i in range(n):
        base = r.randint(0, 255, (size, size, 3)).astype(np.uint8)
        noisy = np.clip(
            base.astype(np.int16) + r.randint(-20, 20, base.shape), 0, 255
        ).astype(np.uint8)
        Image.fromarray(base).save(os.path.join(roots[0], f"{i:04d}.png"))
        Image.fromarray(noisy).save(os.path.join(roots[1], f"{i:04d}.png"))
    return roots


def _write_lpips_fixture(path, seed=0):
    """Synthetic weights in the documented merged AlexNet+LPIPS layout."""
    from distrifuser_tpu.utils import metrics as m

    r = np.random.RandomState(seed)
    state = {}
    for i, (co, ci, k, _, _, _) in zip(m._ALEX_IDX, m._ALEX_CONVS):
        state[f"features.{i}.weight"] = torch.tensor(
            r.randn(co, ci, k, k).astype(np.float32) * 0.05
        )
        state[f"features.{i}.bias"] = torch.zeros(co)
    for i, (co, _, _, _, _, _) in enumerate(m._ALEX_CONVS):
        state[f"lin{i}.model.1.weight"] = torch.tensor(
            np.abs(r.randn(1, co, 1, 1).astype(np.float32))
        )
    torch.save(state, path)


class _TinyExtractor(torch.nn.Module):
    """Random-weight stand-in with the pt_inception contract:
    [N,3,299,299] float in [0,1] -> [N,D] features."""

    def __init__(self, dim=16):
        super().__init__()
        self.conv = torch.nn.Conv2d(3, dim, kernel_size=7, stride=4)
        self.pool = torch.nn.AdaptiveAvgPool2d(1)

    def forward(self, x):
        return self.pool(torch.relu(self.conv(x))).flatten(1)


def _write_fid_fixture(path, seed=0):
    torch.manual_seed(seed)
    mod = torch.jit.script(_TinyExtractor())
    torch.jit.save(mod, path)


def test_compute_metrics_cli_all_three(tmp_path):
    root0, root1 = _write_image_dirs(tmp_path)
    lpips_path = str(tmp_path / "lpips_fixture.pth")
    fid_path = str(tmp_path / "fid_fixture.pt")
    _write_lpips_fixture(lpips_path)
    _write_fid_fixture(fid_path)

    out = subprocess.run(
        [sys.executable, CLI,
         "--input_root0", root0, "--input_root1", root1,
         "--lpips_weights", lpips_path, "--fid_weights", fid_path,
         "--batch_size", "4"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert out.returncode == 0, out.stderr
    psnr_m = re.search(r"PSNR: ([\d.]+) dB", out.stdout)
    lpips_m = re.search(r"LPIPS: ([\d.]+)", out.stdout)
    fid_m = re.search(r"FID: ([\d.]+)", out.stdout)
    assert psnr_m and lpips_m and fid_m, out.stdout
    # same-vs-noisy pairs: PSNR finite and plausible, LPIPS/FID >= 0 finite
    assert 5.0 < float(psnr_m.group(1)) < 60.0
    assert np.isfinite(float(lpips_m.group(1)))
    assert np.isfinite(float(fid_m.group(1)))
    assert "unavailable" not in out.stdout


def test_compute_metrics_cli_identical_dirs_degenerate(tmp_path):
    """Identical dirs: FID ~ 0 and LPIPS ~ 0 pin the metric conventions."""
    root0, _ = _write_image_dirs(tmp_path)
    lpips_path = str(tmp_path / "lpips_fixture.pth")
    fid_path = str(tmp_path / "fid_fixture.pt")
    _write_lpips_fixture(lpips_path)
    _write_fid_fixture(fid_path)

    out = subprocess.run(
        [sys.executable, CLI,
         "--input_root0", root0, "--input_root1", root0,
         "--lpips_weights", lpips_path, "--fid_weights", fid_path],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": REPO},
    )
    assert out.returncode == 0, out.stderr
    lpips_m = re.search(r"LPIPS: ([\d.]+)", out.stdout)
    fid_m = re.search(r"FID: (-?[\d.e+-]+)", out.stdout)
    assert float(lpips_m.group(1)) < 1e-6
    assert abs(float(fid_m.group(1))) < 1e-3
