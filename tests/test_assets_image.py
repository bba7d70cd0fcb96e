"""Thin assets image + debug exposer consistency (VERDICT r3 missing #4/#5).

The assets image (build/Dockerfile.assets) carries only the control plane:
orchestrate/, the stdlib-only serve modules (asgi/httpd), loadgen, and the
measurement scripts — no jax/torch/model stack. These tests pin (a) the
light-import property the image depends on, hermetically, and (b) that the
Dockerfile's COPY set and the debug exposer's label contract stay coherent.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BLOCKER = r"""
import sys

FORBIDDEN = {"jax", "jaxlib", "flax", "torch", "transformers", "numpy",
             "optax", "orbax"}

class Block:
    def find_spec(self, name, path=None, target=None):
        if name.split(".")[0] in FORBIDDEN:
            raise ImportError(f"assets image has no {name!r}")
        return None

sys.meta_path.insert(0, Block())

# exactly what the assets image runs (Dockerfile.assets COPY set)
from scalable_hw_agnostic_inference_tpu.orchestrate import (  # noqa: F401
    capacity_checker,
    cova,
    load_sim,
)
from scalable_hw_agnostic_inference_tpu.serve import asgi, httpd  # noqa: F401
from scalable_hw_agnostic_inference_tpu.serve.asgi import App     # noqa: F401
from scalable_hw_agnostic_inference_tpu.serve.httpd import Server  # noqa: F401
print("light-import ok")
"""


def test_control_plane_imports_without_model_stack():
    r = subprocess.run(
        [sys.executable, "-c", BLOCKER], capture_output=True, text=True,
        cwd=ROOT, timeout=120,
        env={**os.environ, "PYTHONPATH": ROOT,
             "PYTHONNOUSERSITE": "1"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "light-import ok" in r.stdout


def test_dockerfile_assets_copies_only_the_control_plane():
    text = open(os.path.join(ROOT, "build", "Dockerfile.assets")).read()
    for needed in ("orchestrate/", "serve/asgi.py", "serve/httpd.py",
                   "kvtier/affinity.py",  # cova's prefix-affinity digest
                   "native/loadgen", "breaking_point.py", "kubectl"):
        assert needed in text, f"Dockerfile.assets must ship {needed}"
    # instructions only (comments may NAME the excluded trees)
    instructions = "\n".join(
        ln for ln in text.splitlines()
        if ln.strip().startswith(("COPY", "RUN", "ADD")))
    for heavy in ("models/", "engine/", "compilectl", "jax", "torch",
                  "transformers", "flax"):
        assert heavy not in instructions, (
            f"Dockerfile.assets must NOT ship {heavy}")


def test_base_image_pinning_contract():
    """build.sh resolves BASE_IMAGE through base-images.lock (mirrored,
    digest-pinned — the reference's DLC-mirroring capability); the lock and
    the mirror script agree on format and naming."""
    build_sh = open(os.path.join(ROOT, "build", "build.sh")).read()
    lock = open(os.path.join(ROOT, "build", "base-images.lock")).read()
    mirror = open(os.path.join(ROOT, "build", "mirror-base.sh")).read()
    assert "base-images.lock" in build_sh
    assert "base-images.lock" in mirror and "--refresh" in mirror
    entries = [ln.split() for ln in lock.splitlines()
               if ln.strip() and not ln.startswith("#")]
    assert any(e[0] == "python:3.12-slim" for e in entries)
    for e in entries:     # "<image>" or "<image> <sha256:...>"
        assert len(e) <= 2
        if len(e) == 2:
            assert e[1].startswith("sha256:")
    # the same naming function on both sides: ':'/'/' -> '-'
    assert "tr ':/' '--'" in build_sh and "//[:\\/]/-" in mirror


def test_mirror_script_records_mirror_digest_and_preserves_lock(tmp_path):
    """mirror-base.sh must (a) pin the digest THE MIRROR serves after push
    (the upstream index digest would 404 there), (b) pass comment/blank
    lines through untouched, (c) skip already-pinned entries without
    pulling. Run against a stub docker."""
    import shutil
    import stat

    work = tmp_path / "build"
    work.mkdir()
    shutil.copy(os.path.join(ROOT, "build", "mirror-base.sh"),
                work / "mirror-base.sh")
    (work / "base-images.lock").write_text(
        "# header comment\n"
        "\n"
        "python:3.12-slim\n"
        "debian:bookworm sha256:" + "a" * 64 + "\n")
    bin_ = tmp_path / "bin"
    bin_.mkdir()
    calls = tmp_path / "calls.log"
    docker = bin_ / "docker"
    docker.write_text(f"""#!/usr/bin/env bash
echo "$@" >> {calls}
case "$1" in
  inspect) echo "mirror.example/base/python-3.12-slim@sha256:{'b' * 64}" ;;
esac
exit 0
""")
    docker.chmod(docker.stat().st_mode | stat.S_IEXEC)
    env = {**os.environ, "PATH": f"{bin_}:{os.environ['PATH']}",
           "MIRROR_REPO": "mirror.example/base"}
    r = subprocess.run(["bash", str(work / "mirror-base.sh")],
                       capture_output=True, text=True, env=env, timeout=60)
    assert r.returncode == 0, r.stderr
    lock = (work / "base-images.lock").read_text()
    assert lock.startswith("# header comment\n\n")          # (b)
    assert f"python:3.12-slim sha256:{'b' * 64}" in lock    # (a) mirror's
    assert f"debian:bookworm sha256:{'a' * 64}" in lock     # (c) untouched
    log = calls.read_text()
    assert "pull debian:bookworm" not in log                # (c) no pull
    assert "push mirror.example/base/python-3.12-slim:pinned" in log


def test_cloudbuild_resolves_base_through_lock():
    """CI must ship from the pinned mirror, not the mutable upstream tag —
    every docker build step consumes the resolve-base output."""
    text = open(os.path.join(ROOT, "build", "cloudbuild.yaml")).read()
    assert "base-images.lock" in text
    assert text.count("/workspace/base_image") >= 4   # 1 write + 3 builds
    assert "BASE_IMAGE=python:3.12-slim" not in text  # no hardcoded base


def test_debug_exposer_label_contract():
    sh = open(os.path.join(ROOT, "deploy", "debug",
                           "create_node_port_svc.sh")).read()
    tmpl = open(os.path.join(ROOT, "deploy", "debug",
                             "node-port-svc-template.yaml")).read()
    # the label key the script writes is the one the template selects on
    assert 'inferencepod=$POD_NAME' in sh
    assert "inferencepod: $POD_NAME" in tmpl
    assert "type: NodePort" in tmpl
    assert "envsubst" in sh
    # debug services must never join routing (no albapp label); the
    # template's comment may explain this, so scan yaml lines only
    yaml_lines = [ln for ln in tmpl.splitlines()
                  if not ln.lstrip().startswith("#")]
    assert not any("albapp" in ln for ln in yaml_lines)
