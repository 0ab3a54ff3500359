"""The benchmark's own yardstick: nothing here imports the program except
where a function says so, and nothing in the program imports this."""
