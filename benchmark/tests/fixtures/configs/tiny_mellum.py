"""The real configuration's module, on the fixture's toy sizes."""

from benchmark.configs import mellum2_12b as real

build = real.build
compare = real.compare
