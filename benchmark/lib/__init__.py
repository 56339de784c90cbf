"""The yardstick's own code: nothing here is imported by the program."""
