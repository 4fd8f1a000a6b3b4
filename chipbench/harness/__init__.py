"""The benchmark's harness: finds a cell's configuration, traffic, system
module and metric readers by name, drives the measured window, reduces the
profiler trace and prints the result line."""
