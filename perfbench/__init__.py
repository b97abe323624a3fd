"""perfbench: the repo's benchmark. Everything here is the yardstick;
the program under test is `mxnet_tpu`, reached only through the family
modules (`perfbench/families/`). See PERF.md."""
