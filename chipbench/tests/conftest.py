"""The benchmark's own tests run on the CPU at tiny sizes: four virtual
devices for the spatial cell, the stock conv lowering so that the program's
float32 twin and the plain reference round alike."""

import os
import sys

import jax

jax.config.update("jax_num_cpu_devices", 4)
jax.config.update("jax_platforms", "cpu")
os.environ["MPI4DL_TPU_CONV_IMPL"] = "xla"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
