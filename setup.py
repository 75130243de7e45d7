"""Build hook: compile the native scheduler into the package tree.

The C++ scheduler (native/src/scheduler.cc) is optional — the pure-Python
planner is a full fallback — so a missing compiler degrades gracefully
rather than failing the install. (The runtime also builds it on demand at
first import; see quest_tpu/native/__init__.py.)
"""

import os
import subprocess
import sys
from pathlib import Path

from setuptools import setup
from setuptools.command.build_py import build_py


class BuildWithNative(build_py):
    def run(self):
        root = Path(__file__).parent
        src = root / "native" / "src" / "scheduler.cc"
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "quest_tpu_hosttag",
            root / "quest_tpu" / "native" / "hosttag.py")
        hosttag = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(hosttag)
        if src.exists():
            out = (root / "quest_tpu" / "native"
                   / f"libquest_sched.{hosttag.build_tag(str(src))}.so")
            try:
                subprocess.run(
                    [os.environ.get("CXX", "g++"), *hosttag.BASE_FLAGS,
                     "-shared", "-o", str(out), str(src)],
                    check=True, timeout=300)
            except (subprocess.SubprocessError, OSError) as e:
                print(f"warning: native scheduler build skipped ({e}); "
                      "the pure-Python planner will be used", file=sys.stderr)
        super().run()


setup(cmdclass={"build_py": BuildWithNative})
