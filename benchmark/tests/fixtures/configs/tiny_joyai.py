"""The real configuration's module, on the fixture's toy sizes."""

from benchmark.configs import joyai_llm_flash as real

build = real.build
compare = real.compare
