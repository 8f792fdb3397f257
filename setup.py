"""Packaging: `pip install .` builds the native coordination core and
installs the `horovodrun` console script (the reference's setup.py
drives CMake the same way; our native build is a plain Makefile)."""

import subprocess
import sys
from pathlib import Path

from setuptools import find_packages, setup
from setuptools.command.build_py import build_py

ROOT = Path(__file__).parent


class BuildNativeThenPy(build_py):
    def run(self):
        subprocess.check_call(["make", "-C", str(ROOT / "native")])
        lib = ROOT / "native" / "libhorovod_tpu_core.so"
        target_pkg = ROOT / "horovod_tpu" / "common"
        # Ship the shared library inside the package so ctypes finds it
        # without the source tree (basics.py checks the package dir
        # first, then the native/ build tree).
        if lib.exists():
            import shutil
            shutil.copy2(lib, target_pkg / lib.name)
        super().run()


setup(
    name="horovod-tpu",
    version="0.1.0",
    description=("TPU-native distributed training framework with "
                 "Horovod's product surface"),
    python_requires=">=3.10",
    packages=find_packages(include=["horovod_tpu", "horovod_tpu.*"]),
    package_data={"horovod_tpu.common": ["libhorovod_tpu_core.so"]},
    install_requires=["numpy", "cloudpickle", "pyyaml"],
    extras_require={
        # Built and tested against jax/jaxlib 0.9.0 only: the code uses
        # jax.shard_map(axis_names=, check_vma=), lax.pcast,
        # lax.axis_size and jax.typeof(...).vma directly, no shim.
        "jax": ["jax>=0.9.0", "optax"],
        "torch": ["torch"],
        "ray": ["ray"],
        "spark": ["pyspark"],
    },
    entry_points={
        "console_scripts": [
            "horovodrun = horovod_tpu.runner.launch:main",
        ],
    },
    cmdclass={"build_py": BuildNativeThenPy},
)
