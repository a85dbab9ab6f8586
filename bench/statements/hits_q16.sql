select UserID, SearchPhrase, count(*) as c
from hits
group by UserID, SearchPhrase
order by c desc, UserID, SearchPhrase
limit 10
