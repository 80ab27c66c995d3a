"""Per-pass fixture tests for tools.analyze: for each of the four project
passes, a snippet it MUST flag and a near-identical snippet it must NOT
flag (the calibration contract — precision regressions show up here)."""

import textwrap
from pathlib import Path

from tools.analyze import Project, run_passes
from tools.analyze.project import (
    AnalyzeConfig,
    DeadCodeConfig,
    ExhaustivenessConfig,
    LockClassSpec,
    SecretHygieneConfig,
    TracePurityConfig,
)

REPO = Path(__file__).resolve().parent.parent


def make_config(**kw):
    defaults = dict(
        source_roots=("src",),
        lock_classes=(),
        trace=TracePurityConfig(roots=()),
        exhaustiveness=None,
        secrets=SecretHygieneConfig(roots=()),
        dead=DeadCodeConfig(roots=()),
    )
    defaults.update(kw)
    return AnalyzeConfig(**defaults)


def analyze(tmp_path, files, config, select):
    for rel, content in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(content))
    return run_passes(Project(tmp_path, config=config), select=select)


def codes(findings):
    return [f.code for f in findings]


# ---------------------------------------------------------------------------
# lock discipline


LOCK_SPEC = (
    LockClassSpec(
        path="src/state.py", cls="State", locks=("_lock",), guarded=("auto",)
    ),
)

THREAD_SPEC = (
    LockClassSpec(
        path="src/eng.py",
        cls="Eng",
        locks=("_lock",),
        guarded=("_memo",),
        mode="threads",
    ),
)


def test_lock_discipline_flags_unlocked_write_across_await(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/state.py": """
            import asyncio

            class State:
                def __init__(self):
                    self._lock = asyncio.Lock()
                    self._seq = 0

                async def locked(self):
                    async with self._lock:
                        self._seq += 1

                async def racy(self, v):
                    await asyncio.sleep(0)
                    self._seq = v  # write after a suspension, no lock
            """
        },
        make_config(lock_classes=LOCK_SPEC),
        ["lock-discipline"],
    )
    assert codes(findings) == ["LD001"]
    assert "racy" in findings[0].message and "_seq" in findings[0].message


def test_lock_discipline_allows_sync_and_init_writes(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/state.py": """
            import asyncio

            class State:
                def __init__(self):
                    self._lock = asyncio.Lock()
                    self._seq = 0

                def sync_write(self, v):
                    self._seq = v  # loop-atomic: fine in "loop" mode

                async def no_suspension(self, v):
                    self._seq = v  # async but cannot interleave

                async def locked(self, v):
                    async with self._lock:
                        self._seq = v
            """
        },
        make_config(lock_classes=LOCK_SPEC),
        ["lock-discipline"],
    )
    assert findings == []


def test_lock_discipline_threads_mode_flags_sync_writes_and_mutators(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/eng.py": """
            import threading

            class Eng:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._memo = {}

                def ok(self, k, v):
                    with self._lock:
                        self._memo[k] = v

                def bad_assign(self, k, v):
                    self._memo[k] = v

                def bad_mutator(self):
                    self._memo.clear()
            """
        },
        make_config(lock_classes=THREAD_SPEC),
        ["lock-discipline"],
    )
    assert codes(findings) == ["LD001", "LD001"]


def test_lock_discipline_flags_lock_rebind(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/eng.py": """
            import threading

            class Eng:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._memo = {}

                def reset(self):
                    self._lock = threading.Lock()
            """
        },
        make_config(lock_classes=THREAD_SPEC),
        ["lock-discipline"],
    )
    assert codes(findings) == ["LD002"]


def test_lock_discipline_auto_infers_guarded_attrs(tmp_path):
    """An attribute locked ONCE is guarded EVERYWHERE (lock affinity)."""
    findings = analyze(
        tmp_path,
        {
            "src/state.py": """
            import asyncio

            class State:
                def __init__(self):
                    self._lock = asyncio.Lock()
                    self._a = 0
                    self._free = 0

                async def locked(self):
                    async with self._lock:
                        self._a += 1

                async def racy(self):
                    await asyncio.sleep(0)
                    self._a = 9      # inferred-guarded: flagged
                    self._free = 9   # never locked anywhere: not guarded
            """
        },
        make_config(lock_classes=LOCK_SPEC),
        ["lock-discipline"],
    )
    assert codes(findings) == ["LD001"]
    assert "_a" in findings[0].message


def test_lock_discipline_condvar_wait_counts_as_suspension(tmp_path):
    """`await self._cond.wait()` inside `async with self._cond` both
    suspends AND releases the lock — an unlocked write elsewhere in the
    same method races it and must be flagged (the ClientState/PeerState
    pattern this pass exists for)."""
    findings = analyze(
        tmp_path,
        {
            "src/state.py": """
            import asyncio

            class State:
                def __init__(self):
                    self._lock = asyncio.Condition()
                    self._seq = 0

                async def bump(self):
                    async with self._lock:
                        while self._seq == 0:
                            await self._lock.wait()
                    self._seq += 1  # unlocked, after a real suspension
            """
        },
        make_config(
            lock_classes=(
                LockClassSpec(
                    path="src/state.py",
                    cls="State",
                    locks=("_lock",),
                    guarded=("_seq",),
                ),
            )
        ),
        ["lock-discipline"],
    )
    assert codes(findings) == ["LD001"]


def test_lock_discipline_noqa(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/eng.py": """
            import threading

            class Eng:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._memo = {}

                def justified(self, k, v):
                    self._memo[k] = v  # noqa: LD001
            """
        },
        make_config(lock_classes=THREAD_SPEC),
        ["lock-discipline"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# trace purity


TRACE_CFG = TracePurityConfig(roots=("src",))


def test_trace_purity_flags_reachable_impurity(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/kernel.py": """
            import jax
            import numpy as np

            def _helper(x):
                print("tracing", x)       # TP101
                return np.asarray(x) + 1  # TP102: np on a traced value

            def _verify_one(x):
                if x > 0:                 # TP105: branch on a tracer
                    return _helper(x)
                return x

            verify_kernel = jax.jit(jax.vmap(_verify_one))
            """
        },
        make_config(trace=TRACE_CFG),
        ["trace-purity"],
    )
    assert sorted(codes(findings)) == ["TP101", "TP102", "TP105"]


def test_trace_purity_ignores_host_side_and_static(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/kernel.py": """
            import jax
            import numpy as np

            def to_limbs(x: int):
                # host-static param: np here is trace-time constant building
                if not 0 <= x < 2**256:
                    raise ValueError("range")
                return np.array([x & 0xFFFF], dtype=np.uint32)

            def _verify_one(x):
                k = np.uint32(7)          # np on a literal: constant
                if x.shape[0] > 4:        # shape is static under trace
                    return x * k
                return x

            verify_kernel = jax.jit(jax.vmap(_verify_one))

            def host_driver(items):
                # NOT reachable from any jit root: impurity is fine here
                print(len(items))
                return np.asarray(items)
            """
        },
        make_config(trace=TRACE_CFG),
        ["trace-purity"],
    )
    assert findings == []


def test_trace_purity_cross_module_reachability(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/limbs.py": """
            import time

            def slow_add(a, b):
                time.sleep(0.1)  # TP103, reachable from kernel.py's root
                return a + b
            """,
            "src/kernel.py": """
            import jax
            from limbs import slow_add

            def _one(x):
                return slow_add(x, x)

            k = jax.jit(_one)
            """,
        },
        make_config(trace=TRACE_CFG),
        ["trace-purity"],
    )
    assert codes(findings) == ["TP103"]
    assert findings[0].path == "src/limbs.py"


def test_trace_purity_flags_global_statement(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/kernel.py": """
            import jax

            _COUNT = 0

            def _one(x):
                global _COUNT   # TP104
                _COUNT += 1
                return x

            k = jax.jit(_one)
            """
        },
        make_config(trace=TRACE_CFG),
        ["trace-purity"],
    )
    assert codes(findings) == ["TP104"]


# ---------------------------------------------------------------------------
# exhaustiveness


def _msg_tree(
    *,
    drop_codec_marshal=False,
    drop_codec_unmarshal=False,
    drop_authen=False,
    drop_handler=False,
):
    codec_marshal = "" if drop_codec_marshal else """
    if isinstance(m, Ping):
        return b"\\x01"
"""
    codec_unmarshal = "" if drop_codec_unmarshal else """
    if data[0] == 1:
        return Ping(replica_id=0)
"""
    authen = "" if drop_authen else """
    if isinstance(m, Ping):
        return b"PING"
"""
    handler = "" if drop_handler else """
        if isinstance(msg, Ping):
            return True
"""
    return {
        "src/message.py": """
class Message:
    KIND = "?"

class Ping(Message):
    KIND = "PING"
    replica_id: int
    signature: bytes = b""

SIGNED_MESSAGES = (Ping,)
""",
        "src/codec.py": f"""
from message import Message, Ping

def marshal(m):{codec_marshal}
    raise ValueError(m)

def _unmarshal_at(data, off):{codec_unmarshal}
    raise ValueError(data)
""",
        "src/authen.py": f"""
from message import Ping

def _authen_bytes(m):{authen}
    raise TypeError(m)
""",
        "src/handlers.py": f"""
from message import Ping

class H:
    async def validate_message(self, msg):{handler or "        pass"}
    async def process_message(self, msg):{handler or "        pass"}
""",
    }


EX_CFG = ExhaustivenessConfig(
    message_module="src/message.py",
    codec_module="src/codec.py",
    authen_module="src/authen.py",
    handler_module="src/handlers.py",
)


def test_exhaustiveness_clean_when_fully_wired(tmp_path):
    findings = analyze(
        tmp_path, _msg_tree(), make_config(exhaustiveness=EX_CFG), ["exhaustiveness"]
    )
    assert findings == []


def test_exhaustiveness_flags_each_missing_layer(tmp_path):
    for kw, expect in (
        ({"drop_codec_marshal": True}, "EX201"),
        ({"drop_codec_unmarshal": True}, "EX202"),
        ({"drop_authen": True}, "EX203"),
        ({"drop_handler": True}, "EX204"),
    ):
        tree = tmp_path / expect
        tree.mkdir()
        findings = analyze(
            tree, _msg_tree(**kw), make_config(exhaustiveness=EX_CFG), ["exhaustiveness"]
        )
        assert expect in codes(findings), (kw, findings)


def test_exhaustiveness_handler_alternative_verified(tmp_path):
    cfg = ExhaustivenessConfig(
        message_module="src/message.py",
        codec_module="src/codec.py",
        authen_module="src/authen.py",
        handler_module="src/handlers.py",
        handler_alternatives={"Ping": ("src/client.py", "client-side kind")},
    )
    # alternative module really handles it -> clean even though the
    # dispatch functions don't mention Ping
    files = _msg_tree(drop_handler=True)
    files["src/client.py"] = "from message import Ping\n\ndef on(msg):\n    return isinstance(msg, Ping)\n"
    findings = analyze(
        tmp_path / "ok", files, make_config(exhaustiveness=cfg), ["exhaustiveness"]
    )
    assert findings == []

    # alternative module does NOT handle it -> stale exemption (EX205)
    files2 = _msg_tree(drop_handler=True)
    files2["src/client.py"] = "def on(msg):\n    return False\n"
    findings = analyze(
        tmp_path / "stale", files2, make_config(exhaustiveness=cfg), ["exhaustiveness"]
    )
    assert "EX205" in codes(findings)


def test_exhaustiveness_on_this_repo_is_clean():
    findings = run_passes(Project(REPO), select=["exhaustiveness"])
    assert findings == []


# ---------------------------------------------------------------------------
# secret hygiene


SH_CFG = SecretHygieneConfig(roots=("src",))


def test_secret_hygiene_flags_interpolation_and_logging(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/ks.py": """
            import logging

            log = logging.getLogger("x")

            def leak(private_key, seed):
                msg = f"loaded key {private_key!r}"     # SH301
                log.info("seed is %s", seed)            # SH302
                print(repr(private_key))                # SH302 (print arg)
                return msg
            """
        },
        make_config(secrets=SH_CFG),
        ["secret-hygiene"],
    )
    got = codes(findings)
    assert "SH301" in got and got.count("SH302") == 2


def test_secret_hygiene_allows_public_names_and_truthiness(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/ks.py": """
            def fine(pub_key, keyspec, env_key, mac_keys, kid):
                a = f"public key {pub_key.hex()} spec {keyspec}"
                b = f"id {kid}, CONSENSUS_{env_key}"
                c = "with MACs" if mac_keys is not None else "no MACs"
                d = f"have {len(mac_keys)} macs"
                return a, b, c, d
            """
        },
        make_config(secrets=SH_CFG),
        ["secret-hygiene"],
    )
    assert findings == []


def test_secret_hygiene_flags_hex_and_format_sinks(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/ks.py": """
            def leak(sealed_blob, priv):
                a = "blob: " + sealed_blob.hex()        # SH303
                b = "{}".format(priv)                    # SH303
                c = "p=%s" % priv                        # SH303
                return a, b, c
            """
        },
        make_config(secrets=SH_CFG),
        ["secret-hygiene"],
    )
    assert codes(findings) == ["SH303", "SH303", "SH303"]


def test_secret_hygiene_on_this_repo_is_clean():
    findings = run_passes(Project(REPO), select=["secret-hygiene"])
    assert findings == []


# ---------------------------------------------------------------------------
# dead code


DC_CFG = DeadCodeConfig(roots=("src",))


def test_dead_code_flags_unused_import_and_local(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/m.py": """
            import os
            import sys
            from typing import Dict, List

            def f():
                unused = sys.platform     # DC402
                d: Dict = {}
                return d
            """
        },
        make_config(dead=DC_CFG),
        ["dead-code"],
    )
    assert sorted(codes(findings)) == ["DC401", "DC401", "DC402"]
    msgs = " ".join(f.message for f in findings)
    assert "os" in msgs and "List" in msgs and "unused" in msgs


def test_dead_code_ignores_class_attributes_in_function_scope(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/m.py": """
            def make():
                class Cfg:
                    retries = 3
                return Cfg
            """
        },
        make_config(dead=DC_CFG),
        ["dead-code"],
    )
    assert findings == []


def test_dead_code_respects_reexports_and_annotations(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/pkg/__init__.py": "from .m import helper\n",
            "src/pkg/m.py": """
            from typing import Optional

            def helper(x: "Optional[int]"):
                return x
            """,
            "src/closure.py": """
            def outer():
                captured = 1
                def inner():
                    return captured
                return inner
            """,
        },
        make_config(dead=DeadCodeConfig(roots=("src",))),
        ["dead-code"],
    )
    assert findings == []

# ---------------------------------------------------------------------------
# async hygiene


def ah_config(**kw):
    from tools.analyze.project import AsyncHygieneConfig

    return make_config(async_hygiene=AsyncHygieneConfig(roots=("src",), **kw))


def test_async_hygiene_flags_blocking_sink_through_sync_helper(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/app.py": """
            import time

            def helper():
                time.sleep(0.5)

            async def handler():
                helper()
            """,
        },
        ah_config(),
        ["async-hygiene"],
    )
    assert codes(findings) == ["AH101"]
    assert "handler" in findings[0].message


def test_async_hygiene_cross_module_chain_and_witness_path(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/util.py": """
            import subprocess

            def probe():
                subprocess.run(["true"])
            """,
            "src/app.py": """
            from src.util import probe

            def shim():
                probe()

            async def serve():
                shim()
            """,
        },
        ah_config(),
        ["async-hygiene"],
    )
    assert codes(findings) == ["AH101"]
    assert "serve" in findings[0].message and "probe" in findings[0].message


def test_async_hygiene_executor_handoff_is_whitelisted(tmp_path):
    # The SAME blocking helper is fine when it only runs behind
    # asyncio.to_thread / run_in_executor: the hand-off suspends.
    findings = analyze(
        tmp_path,
        {
            "src/app.py": """
            import asyncio
            import time

            def helper():
                time.sleep(0.5)

            async def handler():
                await asyncio.to_thread(helper)

            async def handler2():
                loop = asyncio.get_running_loop()
                await loop.run_in_executor(None, helper)
            """,
        },
        ah_config(),
        ["async-hygiene"],
    )
    assert findings == []


def test_async_hygiene_boundary_config_excludes_function(tmp_path):
    files = {
        "src/app.py": """
        import time

        def engine_step():
            time.sleep(0.001)

        async def run():
            engine_step()
        """,
    }
    flagged = analyze(tmp_path / "a", files, ah_config(), ["async-hygiene"])
    assert codes(flagged) == ["AH101"]
    excused = analyze(
        tmp_path / "b",
        files,
        ah_config(
            boundary={"src/app.py::engine_step": "micro-bounded by design"}
        ),
        ["async-hygiene"],
    )
    assert excused == []


def test_async_hygiene_sync_io_and_lock_and_pow(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/app.py": """
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()

                async def dump(self, path, doc):
                    with open(path, "w") as fh:
                        fh.write(doc)

                async def bump(self):
                    with self._lock:
                        pass

            async def modexp(x):
                return pow(x, 65537, 2**255 - 19)
            """,
        },
        ah_config(),
        ["async-hygiene"],
    )
    assert sorted(codes(findings)) == ["AH102", "AH103", "AH104"]


def test_async_hygiene_sync_context_not_flagged(tmp_path):
    # The same sinks OUTSIDE the loop-reachable graph are fine.
    findings = analyze(
        tmp_path,
        {
            "src/tool.py": """
            import time

            def main():
                time.sleep(1)
                with open("x") as fh:
                    return fh.read()
            """,
        },
        ah_config(),
        ["async-hygiene"],
    )
    assert findings == []


def test_async_hygiene_loop_scheduled_reference_is_a_root(tmp_path):
    # A SYNC function handed to call_soon runs on the loop: its sinks count.
    findings = analyze(
        tmp_path,
        {
            "src/app.py": """
            import asyncio
            import time

            def tick():
                time.sleep(0.1)

            def arm(loop):
                loop.call_soon(tick)
            """,
        },
        ah_config(),
        ["async-hygiene"],
    )
    assert codes(findings) == ["AH101"]


def test_async_hygiene_on_this_repo_is_clean():
    from tools.analyze.project import default_config

    project = Project(REPO, config=default_config())
    assert run_passes(project, select=["async-hygiene"]) == []


# ---------------------------------------------------------------------------
# task lifecycle


def tl_config():
    from tools.analyze.project import TaskLifecycleConfig

    return make_config(tasks=TaskLifecycleConfig(roots=("src",)))


def test_task_lifecycle_flags_dropped_and_unretained_tasks(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/app.py": """
            import asyncio

            async def work():
                pass

            async def bare():
                asyncio.create_task(work())

            async def named_but_dropped():
                t = asyncio.create_task(work())
                print("unrelated", 1)

            async def conditional_dropped(flag):
                t = (asyncio.create_task(work()) if flag else None)
            """,
        },
        tl_config(),
        ["task-lifecycle"],
    )
    assert codes(findings) == ["TL601", "TL601", "TL601"]


def test_task_lifecycle_retention_evidence_not_flagged(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/app.py": """
            import asyncio

            async def work():
                pass

            class H:
                def __init__(self):
                    self._bg_tasks = set()

                def spawn(self):
                    t = asyncio.get_running_loop().create_task(work())
                    self._bg_tasks.add(t)
                    t.add_done_callback(self._bg_tasks.discard)
                    return t

                def attr_store(self):
                    self._task = asyncio.create_task(work())

            async def awaited():
                await asyncio.create_task(work())

            async def cancelled_then_awaited():
                t = asyncio.create_task(work())
                t.cancel()
                try:
                    await t
                except asyncio.CancelledError:
                    pass

            async def callback_only():
                t = asyncio.create_task(work())
                t.add_done_callback(lambda _t: None)

            async def gathered():
                t = asyncio.create_task(work())
                await asyncio.gather(t)
            """,
        },
        tl_config(),
        ["task-lifecycle"],
    )
    assert findings == []


def test_task_lifecycle_flags_unsnapshotted_tracked_set_iteration(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/app.py": """
            import asyncio

            class H:
                def __init__(self):
                    self._bg_tasks = set()

                def spawn(self, coro):
                    t = asyncio.create_task(coro)
                    self._bg_tasks.add(t)
                    t.add_done_callback(self._bg_tasks.discard)
                    return t

                def cancel_all(self):
                    for t in self._bg_tasks:
                        t.cancel()
            """,
        },
        tl_config(),
        ["task-lifecycle"],
    )
    assert codes(findings) == ["TL602"]
    assert "list(" in findings[0].message


def test_task_lifecycle_snapshotted_iteration_not_flagged(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/app.py": """
            import asyncio

            class H:
                def __init__(self):
                    self._bg_tasks = set()

                def spawn(self, coro):
                    t = asyncio.create_task(coro)
                    self._bg_tasks.add(t)
                    t.add_done_callback(self._bg_tasks.discard)
                    return t

                def cancel_all(self):
                    for t in list(self._bg_tasks):
                        t.cancel()
            """,
        },
        tl_config(),
        ["task-lifecycle"],
    )
    assert findings == []


def test_task_lifecycle_on_this_repo_is_clean():
    from tools.analyze.project import default_config

    project = Project(REPO, config=default_config())
    assert run_passes(project, select=["task-lifecycle"]) == []


# ---------------------------------------------------------------------------
# schema drift


def sd_files(prom_body, test_body):
    return {
        "obs/prom.py": prom_body,
        "tests/test_pins.py": test_body,
    }


def sd_config():
    from tools.analyze.project import SchemaDriftConfig

    return make_config(
        schema=SchemaDriftConfig(
            prom_module="obs/prom.py",
            pinned_tests=("tests/test_pins.py",),
        )
    )


def test_schema_drift_clean_when_aligned(tmp_path):
    # An f-string family matches its expansions, and a histogram's
    # exposition suffixes match the bare family.
    findings = analyze(
        tmp_path,
        sd_files(
            'FAM = "minbft_commit_seconds"\n'
            "def fam(stage):\n"
            "    return f'minbft_stage_{stage}_total'\n",
            'A = "minbft_commit_seconds_bucket"\n'
            'B = "minbft_stage_prepare_total"\n',
        ),
        sd_config(),
        ["schema-drift"],
    )
    assert findings == []


def test_schema_drift_pinned_prom_names(tmp_path):
    findings = analyze(
        tmp_path,
        sd_files(
            'FAM = "minbft_committed_total"\n',
            'OK = "minbft_committed_total"\n'
            'BAD = "minbft_never_registered_total"\n',
        ),
        sd_config(),
        ["schema-drift"],
    )
    assert codes(findings) == ["SD705"]
    assert "minbft_never_registered_total" in findings[0].message


def test_schema_drift_on_this_repo_is_clean():
    from tools.analyze.project import default_config

    project = Project(REPO, config=default_config())
    assert run_passes(project, select=["schema-drift"]) == []


# ---------------------------------------------------------------------------
# env registry


def er_config():
    from tools.analyze.project import EnvRegistryConfig

    return make_config(
        env=EnvRegistryConfig(roots=("src",), registry="ENV.md")
    )


ER_HEADER = "# Registry\n\n| Variable | Description |\n|---|---|\n"


def test_env_registry_clean_when_registered(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/app.py": 'import os\nV = os.environ.get("MINBFT_KNOB")\n',
            "ENV.md": ER_HEADER + "| `MINBFT_KNOB` | turns the knob |\n",
        },
        er_config(),
        ["env-registry"],
    )
    assert findings == []


def test_env_registry_flags_unregistered_dead_and_undescribed(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/app.py": (
                "import os\n"
                'A = os.environ.get("MINBFT_LIVE")\n'
                'B = os.environ.get("MINBFT_NEW_KNOB")\n'
            ),
            "ENV.md": ER_HEADER
            + "| `MINBFT_LIVE` | TODO: describe |\n"
            + "| `MINBFT_GONE` | removed long ago |\n",
        },
        er_config(),
        ["env-registry"],
    )
    got = sorted(codes(findings))
    assert got == ["ER501", "ER502", "ER503"]


def test_env_registry_prefix_pattern_covers_fstring_sites(tmp_path):
    findings = analyze(
        tmp_path,
        {
            "src/app.py": (
                "import os\n"
                "def get(i):\n"
                '    return os.environ.get(f"MINBFT_CFG{i}_REQUESTS")\n'
            ),
            "ENV.md": ER_HEADER + "| `MINBFT_CFG*` | per-config knobs |\n",
        },
        er_config(),
        ["env-registry"],
    )
    assert findings == []


def test_env_registry_missing_registry_is_one_finding(tmp_path):
    findings = analyze(
        tmp_path,
        {"src/app.py": 'import os\nV = os.environ.get("MINBFT_KNOB")\n'},
        er_config(),
        ["env-registry"],
    )
    assert codes(findings) == ["ER501"]
    assert "registry missing" in findings[0].message


def test_env_registry_write_then_clean(tmp_path):
    from tools.analyze.passes.env_registry import write_registry

    files = {
        "src/app.py": 'import os\nV = os.environ.get("MINBFT_KNOB")\n',
    }
    for rel, content in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(content)
    project = Project(tmp_path, config=er_config())
    relpath, count = write_registry(project)
    assert count == 1
    # freshly generated: every description is a TODO -> ER503 only
    project = Project(tmp_path, config=er_config())
    findings = run_passes(project, select=["env-registry"])
    assert codes(findings) == ["ER503"]
    # describe it -> clean
    reg = tmp_path / relpath
    reg.write_text(reg.read_text().replace("TODO: describe", "the knob"))
    project = Project(tmp_path, config=er_config())
    assert run_passes(project, select=["env-registry"]) == []


def test_env_registry_on_this_repo_is_clean():
    from tools.analyze.project import default_config

    project = Project(REPO, config=default_config())
    assert run_passes(project, select=["env-registry"]) == []
