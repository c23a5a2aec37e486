"""The real configuration's module, on the fixture's toy sizes."""

from benchmark.configs import laguna_s_2_1 as real

build = real.build
compare = real.compare
