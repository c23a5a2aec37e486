"""The real configuration's module, on the fixture's toy sizes."""

from benchmark.configs import granite_4_0_h_micro as real

build = real.build
compare = real.compare
