"""The real configuration's module, on the fixture's toy sizes."""

from benchmark.configs import nemotron_3_nano_30b_a3b as real

build = real.build
compare = real.compare
