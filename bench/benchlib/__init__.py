"""The benchmark's own library: traffic, client, statistics, trace
reduction, cost arithmetic and the correctness check. Nothing here is
imported by the program under test."""
