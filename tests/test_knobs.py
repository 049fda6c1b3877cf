"""The typed KFT_* knob registry (kungfu_tpu/utils/knobs.py).

Pins the parse/fallback contract every migrated call site now depends
on, the call-time `env=` lookup that makes per-job overrides
(Job.extra_env) work, and the docs/knobs.md generation the CI
freshness check enforces.
"""
import importlib.util
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from kungfu_tpu.utils import knobs  # noqa: E402


# ------------------------------------------------------------ typed parse
def test_typed_parse_per_type():
    env = {
        "KFT_SSH": "rsh",                      # str
        "KFT_BASE_PORT": "4000",               # int
        "KFT_HEARTBEAT_S": "0.25",             # float
        "KFT_SIM_LITE": "1",                   # bool
        "KFT_CHAOS_PROPOSE": "[[3, 1], [2, 1]]",  # json
        "KFT_SIM_SLOW_RANKS": "0, 3 ,7",       # intset
    }
    assert knobs.get("KFT_SSH", env=env) == "rsh"
    assert knobs.get("KFT_BASE_PORT", env=env) == 4000
    assert knobs.get("KFT_HEARTBEAT_S", env=env) == 0.25
    assert knobs.get("KFT_SIM_LITE", env=env) is True
    assert knobs.get("KFT_CHAOS_PROPOSE", env=env) == [[3, 1], [2, 1]]
    assert knobs.get("KFT_SIM_SLOW_RANKS", env=env) == {0, 3, 7}


def test_unset_and_empty_fall_back_to_default():
    assert knobs.get("KFT_BASE_PORT", env={}) == 31100
    # "" is uniformly treated as unset (matches the pre-registry
    # `os.environ.get(k) or default` idiom at most call sites)
    assert knobs.get("KFT_BASE_PORT", env={"KFT_BASE_PORT": ""}) == 31100
    assert knobs.raw("KFT_BASE_PORT", env={"KFT_BASE_PORT": ""}) is None
    # per-call default override
    assert knobs.get("KFT_BASE_PORT", env={}, default=7) == 7


@pytest.mark.parametrize("text,expect", [
    ("0", False), ("false", False), ("OFF", False), ("no", False),
    ("", False), ("1", True), ("true", True), ("anything", True),
])
def test_bool_falsey_set(text, expect):
    env = {"KFT_SIM_LITE": text}
    assert knobs.get("KFT_SIM_LITE", env=env) is expect


def test_tristate_bool_default_none():
    # unset -> None, so callers can distinguish "unset" from "forced
    # off" (the chaos runner's data-plane probe override)
    assert knobs.get("KFT_TESTS_DATA_PLANE", env={}) is None
    assert knobs.get("KFT_TESTS_DATA_PLANE",
                     env={"KFT_TESTS_DATA_PLANE": "0"}) is False


def test_malformed_warns_and_falls_back(capsys):
    env = {"KFT_BASE_PORT": "not-a-port"}
    assert knobs.get("KFT_BASE_PORT", env=env) == 31100
    err = capsys.readouterr().err
    assert "malformed" in err and "KFT_BASE_PORT" in err


def test_required_raises_when_unset_or_malformed():
    with pytest.raises(KeyError):
        knobs.get("KFT_CHAOS_OUT", env={})
    # malformed required values may not silently fall back — there is
    # no sane default to fall back to
    with pytest.raises(ValueError):
        knobs.get("KFT_CHAOS_TARGET", env={"KFT_CHAOS_TARGET": "ten"})


def test_unregistered_name_is_a_keyerror():
    with pytest.raises(KeyError):
        # kfcheck: disable=knob-registry  (deliberately unregistered)
        knobs.get("KFT_NO_SUCH_KNOB", env={})
    with pytest.raises(KeyError):
        # kfcheck: disable=knob-registry  (deliberately unregistered)
        knobs.raw("KFT_NO_SUCH_KNOB", env={})


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError):
        knobs._def("KFT_BASE_PORT", "int", 1, "dup", group="Launcher")


# --------------------------------------------------- call-time env contexts
def test_two_concurrent_env_contexts_stay_independent():
    """The registry must read at CALL time against the mapping it is
    given — two jobs' env dicts alternate without bleeding state."""
    job_a = {"KFT_HEARTBEAT_S": "0.5"}
    job_b = {"KFT_HEARTBEAT_S": "7.0"}
    for _ in range(3):
        assert knobs.get("KFT_HEARTBEAT_S", env=job_a) == 0.5
        assert knobs.get("KFT_HEARTBEAT_S", env=job_b) == 7.0
        assert knobs.get("KFT_HEARTBEAT_S", env={}) == 2.0  # default


def test_job_extra_env_reaches_registry_lookups():
    """Job.extra_env is the per-job override channel: the env a Proc is
    spawned with must round-trip through the registry typed."""
    from kungfu_tpu.launcher import Job
    from kungfu_tpu.plan import Cluster, HostList, PeerID

    cluster = Cluster.from_hostlist(HostList.parse("127.0.0.1:2"), 2)
    parent = PeerID("127.0.0.1", 31000)
    slow = Job(prog=sys.executable, args=["-c", "pass"],
               extra_env={"KFT_HEARTBEAT_S": "9.5"})
    fast = Job(prog=sys.executable, args=["-c", "pass"])
    p_slow = slow.new_proc(cluster.workers[0], cluster, 0, parent)
    p_fast = fast.new_proc(cluster.workers[1], cluster, 0, parent)
    assert knobs.get("KFT_HEARTBEAT_S", env=p_slow.env) == 9.5
    assert knobs.get("KFT_HEARTBEAT_S", env=p_fast.env) == 2.0
    # the worker-ABI vars the launcher always sets stay registry-readable
    assert knobs.raw("KFT_SELF_SPEC", env=p_slow.env)
    assert knobs.get("KFT_INIT_CLUSTER_VERSION", env=p_slow.env) == 0


# ------------------------------------------------------------------- docs
def _load_standalone():
    spec = importlib.util.spec_from_file_location(
        "_knobs_standalone", REPO / "kungfu_tpu" / "utils" / "knobs.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_knobs_standalone"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_registry_imports_standalone_without_package():
    """The docs generator loads the registry by file path (no jax, no
    kungfu_tpu import); the module must stay stdlib-only."""
    mod = _load_standalone()
    assert len(mod.KNOBS) == len(knobs.KNOBS)


def test_generated_docs_skip_test_only_and_mark_required():
    text = knobs.generate_docs()
    test_only = [k.name for k in knobs.KNOBS.values() if k.test_only]
    assert test_only, "expected test-only fixtures in the registry"
    for name in test_only:
        # skipped from the tables, named once in the footer
        assert text.count(f"`{name}`") == 1
    assert "(required)" in text
    assert "native C++ transport" in text


def test_docs_knobs_md_is_fresh():
    """Same pin CI enforces (tools/gen_knob_docs.py --check): the
    committed docs/knobs.md must match the registry."""
    committed = (REPO / "docs" / "knobs.md").read_text()
    assert committed == knobs.generate_docs(), \
        "docs/knobs.md is stale - run `make knobs-docs`"


def test_gen_knob_docs_check_cli():
    r = subprocess.run(
        [sys.executable, "tools/gen_knob_docs.py", "--check"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
