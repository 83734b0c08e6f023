"""Build hook: compile the native engine at install time.

Reference parity: the reference's 765-line setup.py exists to probe
MPI/CUDA/NCCL/TF/torch toolchains and build four C++ extensions
(reference setup.py:32-35, 244-465).  None of that probing applies here —
the TPU-native engine (``horovod_tpu/cpp``) depends only on a C++17
compiler and pthreads — so the build step runs the package's own builder
(``horovod_tpu/common/native_build.py``: ``make`` plus the source-digest
stamp the runtime checks before it trusts a library) and leaves
``libhorovod_core.so`` inside the package tree.  If the compile fails (no
compiler on the install host) the install still succeeds and the runtime
retries lazily or runs in pure-Python single-process mode.
"""

import importlib.util
import sys
from pathlib import Path

from setuptools import setup
from setuptools.command.build_py import build_py


class BuildPyWithNative(build_py):
    def run(self):
        # Loaded by path: importing the package would need its run-time
        # dependencies at build time.
        spec = importlib.util.spec_from_file_location(
            "native_build",
            Path(__file__).parent / "horovod_tpu" / "common"
            / "native_build.py")
        native_build = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(native_build)
        if native_build.ensure_native_lib() is None:
            print(
                "warning: native engine build failed; the runtime will "
                "retry lazily or run without the C++ core",
                file=sys.stderr,
            )
        super().run()


setup(cmdclass={"build_py": BuildPyWithNative})
