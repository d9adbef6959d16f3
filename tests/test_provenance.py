"""Capture-provenance record contract (anomod/provenance.py) — the
machinery the round-3 evidence protocol rides on."""

import json

from anomod import provenance


def test_capture_record_is_self_describing():
    rec = provenance.capture_record("m", 1.5, "u", kernel="pallas",
                                    device="TPU v5 lite0")
    assert rec["metric"] == "m" and rec["value"] == 1.5 and rec["unit"] == "u"
    assert rec["kernel"] == "pallas"
    # environment stamps present
    assert rec["jax_version"]
    assert rec["timestamp_utc"].endswith("Z")
    # repo is a git checkout, so a sha must be resolvable
    assert len(rec["git_sha"].split("-")[0]) == 40


def test_write_capture_filename_and_collisions(tmp_path):
    rec = provenance.capture_record("tt_replay_throughput", 2.0, "u",
                                    device="TPU v5 lite0")
    paths = [provenance.write_capture(rec, outdir=str(tmp_path))
             for _ in range(3)]
    assert all(p is not None for p in paths)
    assert len(set(paths)) == 3          # same-second captures never clobber
    assert all("_tpu" in p for p in paths)
    # device-class suffix distinguishes a CPU fallback from an on-chip run
    cpu = provenance.write_capture(
        provenance.capture_record("x", 1.0, "u", device="TFRT_CPU_0"),
        outdir=str(tmp_path))
    assert cpu.endswith("_cpu.json")
    loaded = json.loads(open(paths[0]).read())
    assert loaded["value"] == 2.0


def test_write_capture_never_raises(tmp_path):
    target = tmp_path / "not_a_dir"
    target.write_text("file blocks mkdir")
    rec = provenance.capture_record("m", 1.0, "u")
    assert provenance.write_capture(rec, outdir=str(target / "sub")) is None


def test_git_sha_dirty_only_for_tracked_changes(tmp_path):
    # untracked files (like the capture being written) must NOT dirty the
    # sha — only modified tracked files make the measured tree
    # unreproducible.  Use a scratch repo so the test doesn't depend on
    # this checkout's state.
    import subprocess
    r = tmp_path / "repo"
    r.mkdir()
    subprocess.run(["git", "init", "-q"], cwd=r, check=True)
    (r / "a.txt").write_text("x")
    subprocess.run(["git", "add", "a.txt"], cwd=r, check=True)
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    "commit", "-qm", "c"], cwd=r, check=True)
    clean = provenance.git_sha(cwd=str(r))
    assert clean and not clean.endswith("-dirty")
    (r / "untracked.json").write_text("{}")
    assert provenance.git_sha(cwd=str(r)) == clean
    (r / "a.txt").write_text("changed")
    assert provenance.git_sha(cwd=str(r)).endswith("-dirty")


def test_git_sha_of_a_copy_without_git(tmp_path, monkeypatch):
    # a `git archive` checkout (what the chip machine runs) has no .git:
    # it names its tree in .source_tree, and is '' without one
    monkeypatch.setenv("GIT_CEILING_DIRECTORIES", str(tmp_path))
    copy = tmp_path / "copy"
    copy.mkdir()
    assert provenance.git_sha(cwd=str(copy)) == ""
    (copy / ".source_tree").write_text("0123abcd\n")
    assert provenance.git_sha(cwd=str(copy)) == "tree:0123abcd"
