"""Start-up backend contract: no probe, no CPU fallback.  A command
runs on JAX's default backend; ``--platform`` is the only thing in the
package or the bench that sets ``jax_platforms``; a missing accelerator
is JAX's own start-up error (``chip_smoke.py`` turns it into a non-zero
exit before any work)."""

import os
import re

import jax

from sntc_tpu.app import main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sources():
    yield os.path.join(REPO, "bench.py")
    for dirpath, _dirs, files in os.walk(os.path.join(REPO, "sntc_tpu")):
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def test_only_the_platform_flag_sets_jax_platforms():
    hits = []
    for path in _sources():
        with open(path) as f:
            lines = f.read().splitlines()
        for i, line in enumerate(lines):
            if re.search(r"""config\.update\(\s*["']jax_platforms""", line):
                ctx = "\n".join(lines[max(0, i - 4): i])
                hits.append((os.path.relpath(path, REPO), ctx))
    assert sorted(p for p, _ in hits) == ["bench.py", "sntc_tpu/app.py"]
    for _path, ctx in hits:
        assert re.search(r"if .*args.*platform", ctx), ctx


def test_no_cpu_fallback_left():
    for path in _sources():
        with open(path) as f:
            text = f.read()
        assert "falling back to platform=cpu" not in text, path
        assert "backend_probe" not in text, path
    assert not os.path.exists(
        os.path.join(REPO, "sntc_tpu", "utils", "backend_probe.py")
    )


def test_main_without_platform_leaves_backend_choice_to_jax(
    tmp_path, capsys
):
    before = jax.config.jax_platforms
    assert main(["fsck", str(tmp_path)]) == 0
    assert jax.config.jax_platforms == before
    assert main(["fsck", str(tmp_path), "--platform", "cpu"]) == 0
    assert jax.config.jax_platforms == "cpu"
    capsys.readouterr()
